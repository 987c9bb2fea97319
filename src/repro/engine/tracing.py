"""Host spans of the serving runtime, always on.

``SpanCounters.span(name, **ids)`` does two things at once:

* it enters ``jax.profiler.TraceAnnotation("eei.<name>", **ids)``, so a
  ``jax.profiler`` trace shows the span on the host thread that ran it, on
  the same clock as the device ops (the ids, such as ``stack=<dispatch
  sequence id>``, appear as the event's arguments);
* it adds the span's ``time.perf_counter_ns()`` duration to the owner's
  integer counter ``<name>_ns``.

There is no span buffer and no exporter: the profiler is the exporter, and
the counters are read through the owner's ``stats()``.  With the profiler
off a span costs an inactive annotation, two clock reads and one short
locked add.
"""

from __future__ import annotations

import threading
import time

import jax


class _Span:
    __slots__ = ("_owner", "_key", "_annotation", "_t0")

    def __init__(self, owner: "SpanCounters", name: str, ids: dict):
        self._owner = owner
        self._key = f"{name}_ns"
        self._annotation = jax.profiler.TraceAnnotation(f"eei.{name}", **ids)

    def __enter__(self) -> "_Span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self._owner.add(self._key, time.perf_counter_ns() - self._t0)
        self._annotation.__exit__(*exc)


class SpanCounters:
    """Integer counters fed by spans (and by direct ``add``), guarded by a
    leaf lock of their own so that no span contends for its owner's lock.

    ``span_names`` fixes the ``<name>_ns`` keys and ``extra`` further
    counters, so a snapshot has every key from the start (a reader that
    takes deltas of two snapshots finds them all)."""

    def __init__(self, span_names: tuple, extra: tuple = ()):
        self._zero = dict.fromkeys(
            [f"{name}_ns" for name in span_names] + list(extra), 0)
        self._lock = threading.Lock()
        self._values = dict(self._zero)

    def span(self, name: str, **ids) -> _Span:
        """Context manager timing one ``eei.<name>`` span."""
        return _Span(self, name, ids)

    def add(self, key: str, amount: int) -> None:
        with self._lock:
            self._values[key] += int(amount)

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._values)

    def reset(self) -> None:
        with self._lock:
            self._values = dict(self._zero)
