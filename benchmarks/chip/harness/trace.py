"""Profiler trace of a steady stretch of the window, and its reduction.

A traced run (``--trace 1``) starts JAX's profiler ``lead_s`` into the
window and stops it ``length_s`` later, from a thread of its own so the
traffic driver never waits on it.  The stretch is marked by a host span,
``bench.stretch``; the drivers mark what the host is doing with spans named
``bench.*`` (submit, flush, wait, sleep).

The reduction works on plain event lists, so a recorded trace can be kept
as a small JSON fixture and reduced again in a test:

    {"window": [start_ns, end_ns],
     "devices": {"<plane>": [[name, start_ns, dur_ns], ...], ...},
     "host": [[name, start_ns, dur_ns], ...]}

Device ops are the events of each device plane's ``XLA Ops`` line.  Busy
time is the union of their intervals inside the window; the idle share is
one minus busy over the window.  Each idle gap is labelled with the
innermost ``bench.*`` host span that covers its midpoint.
"""

from __future__ import annotations

import glob
import os
import re
import tempfile
import threading
import time
from typing import Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
STRETCH = "bench.stretch"
TOP = 10


class Stretch:
    """Profile ``[lead_s, lead_s + length_s]`` of the window from a thread.

    ``start()`` at the window's start; ``join()`` once the window closed.
    ``events`` then holds the loaded event lists (``None`` if disabled).
    """

    def __init__(self, enabled: bool, lead_s: float, length_s: float,
                 chips: int):
        self.enabled = enabled
        self.lead_s = lead_s
        self.length_s = length_s
        self.chips = chips
        self.events: Optional[dict] = None
        self.error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        if self.enabled:
            self._thread = threading.Thread(target=self._main,
                                            name="bench-trace")
            self._thread.start()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()
            if self.error is not None:
                raise self.error

    def _main(self) -> None:
        import jax

        try:
            time.sleep(self.lead_s)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # host spans only, no Python calls
            with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
                jax.profiler.start_trace(tmp, profiler_options=options)
                try:
                    with jax.profiler.TraceAnnotation(STRETCH):
                        time.sleep(self.length_s)
                finally:
                    jax.profiler.stop_trace()
                paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                                  recursive=True)
                if not paths:
                    raise RuntimeError("the profiler wrote no xplane file")
                self.events = load_events(sorted(paths)[-1], self.chips)
        except BaseException as exc:  # re-raised in join()
            self.error = exc


#: Device ops shorter than this are counted in busy time and in the op
#: table under one entry, without reading their names: the serial loops of
#: the Krylov path run millions of sub-microsecond ops, and building each
#: one's name (its full HLO text) would make reading a trace take minutes.
SHORT_OP_NS = 2000.0
SHORT_OPS = "(ops under 2 us)"


def op_name(text: str) -> str:
    """The HLO instruction name of a device op's trace name
    (``"%sturm_padded.1 = f32[..] custom-call(..)"`` -> ``"sturm_padded.1"``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def load_events(path: str, chips: int) -> dict:
    """Event lists of one ``.xplane.pb``: the ``XLA Ops`` line of each of
    the first ``chips`` TPU planes, and every host ``bench.*`` span."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    window = None
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match and int(match.group(1)) < chips:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    dur = ev.duration_ns
                    name = SHORT_OPS if dur < SHORT_OP_NS else op_name(ev.name)
                    ops.append([name, ev.start_ns, dur])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
                        if ev.name == STRETCH:
                            window = [ev.start_ns, ev.start_ns + ev.duration_ns]
    if window is None:
        raise RuntimeError(f"no {STRETCH} span in {path}")
    return {"window": window, "devices": devices, "host": host}


def _union(intervals: list) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label(host: list, t: float) -> str:
    """The innermost (latest-starting) host span covering ``t``."""
    best, best_start = "untraced", None
    for name, start, dur in host:
        if name != STRETCH and start <= t <= start + dur:
            if best_start is None or start > best_start:
                best, best_start = name, start
    return best


def reduce(events: dict, top: int = TOP) -> dict:
    """Busy and window seconds, the device ops that took most time, op
    counts and seconds by name, and the longest idle gaps by host span."""
    w0, w1 = events["window"]
    window_ns = w1 - w0
    if window_ns <= 0:
        raise ValueError("empty trace window")
    busy_ns, gaps = [], []
    by_name: dict = {}
    for ops in events["devices"].values():
        clipped = []
        for name, start, dur in ops:
            s, e = max(start, w0), min(start + dur, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            entry = by_name.setdefault(name, [0, 0.0])
            entry[0] += 1
            entry[1] += (e - s) * 1e-9
        merged = _union(clipped)
        busy_ns.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, 0.5 * (s + e)))
    chips = max(len(events["devices"]), 1)
    busy_s = sum(busy_ns) / chips * 1e-9
    ops = sorted(by_name.items(), key=lambda kv: -kv[1][1])
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_s,
        "ops": {name: {"count": c, "seconds": s} for name, (c, s) in ops},
        "device_ops": [[name, s] for name, (_, s) in ops[:top]],
        "idle_gaps": [[_label(events["host"], mid), ns * 1e-9]
                      for ns, mid in gaps[:top]],
    }


def kernel_time(summary: dict, pattern: str) -> Optional[dict]:
    """Calls and seconds of the device ops whose name matches ``pattern``
    (a regular expression), or ``None`` where none ran in the stretch."""
    rx = re.compile(pattern)
    count, seconds = 0, 0.0
    for name, entry in summary["ops"].items():
        if rx.search(name):
            count += entry["count"]
            seconds += entry["seconds"]
    if count == 0:
        return None
    return {"count": count, "seconds": seconds}
