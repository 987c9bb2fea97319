"""EeiServer — concurrent continuous-batching serving runtime for EEI top-k.

``launch/serve.py --eei`` (and anything else serving the paper's workload —
streams of partial eigenpair queries over many small symmetric matrices)
used to run a static, synchronous loop: one fixed ``(b, n, k)`` per process,
``block_until_ready`` after every request, and a fresh XLA compile for every
distinct shape.  This module replaces that loop with a serving runtime:

    submit() ──> request queues (heterogeneous n, k, largest; thread-safe
         │       from any number of producer threads, with optional
         │       ``max_pending`` backpressure: block or raise QueueFull)
         ▼  coalesce: FIFO groups sharing a key (bucket_n, largest)
    admission ──> dynamic stacks (full stacks immediately; *partial* stacks
         │        once their oldest request has lingered ``linger_ms`` —
         ▼        sparse streams drain with no explicit flush())
    ProgramCache (bucket -> AOT-compiled executable; hit / miss / compile
         │        counters; internally locked, shareable between servers)
         ▼
    async dispatch (≤ max_inflight stacks of device buffers outstanding)
         │
         ▼
    retire ──> completion futures (per-request slices out of the padded
               stack; guard rows never escape; a failed dispatch or a
               closed server resolves futures with the error — callers
               blocked on ``future.result()`` are never stranded)

Two run modes share every dispatch/retire/cache path:

* **caller-driven** (``linger_ms=None``, the default): no background
  threads.  ``submit()`` dispatches full stacks inline (via ``pump()``)
  and ``flush()`` drains partial stacks and blocks until every future
  resolves — the PR-3 behavior, still thread-safe under the server lock.
* **threaded** (``linger_ms`` set): a background *admission* thread forms
  stacks — full groups immediately, partial groups once their oldest
  request has waited ``linger_ms`` — and a *retire* thread blocks on the
  oldest in-flight stack and resolves futures, so producers never block on
  device sync.  ``flush()`` becomes a drain barrier; ``close()`` drains
  everything, joins both threads, and resolves any late ``submit()`` with
  an already-set :class:`ServerClosed` error.

Threading model (see ``docs/ARCHITECTURE.md`` for the full write-up):
one re-entrant server lock guards queues, the in-flight deque and all
counters; a single condition variable (``_cv``) carries every wakeup
(new work, linger deadline, in-flight capacity, drain progress).  The
``ProgramCache`` lock is a *leaf*: the cache never calls back into the
server, so lock order is server lock -> cache lock and never the reverse.
The admission thread compiles and launches *outside* the server lock, so a
multi-second XLA compile never blocks ``submit()``.

Shape bucketing is what bounds compilation: every request executes through
one of a small set of padded shapes, so a 100-request mixed stream compiles
at most one program per distinct bucket instead of one per distinct request
shape.  ``n`` rounds up to the kernel block-grid granule
(``kernels/blocks.clamp_block``'s align-8 sublane grid that the calibrated
tile shapes clamp to); ``b`` and ``k`` round to powers of two.

Matrices are padded from ``(n, n)`` to ``(bn, bn)`` as ``diag(A, c * I)``
with the guard value ``c`` placed strictly outside the spectrum (Gershgorin
bound) on the side *away* from the requested extreme, so the guard
eigenvalues can never enter a top-k (or bottom-k) window and the A-block
eigenpairs are preserved exactly (the padded block decouples: Householder,
Sturm and the sign recurrence all see an exactly-zero junction, which
``tridiagonal_signs`` handles as a restart).

The ``sharded`` backend serves through the same path: pow2 stack buckets
are rounded up to the mesh batch axis, so a serve mode runs on a
multi-device mesh (``serve.py --eei --sharded``; tests force a 2-device
host mesh via ``XLA_FLAGS=--xla_force_host_platform_device_count``).
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import threading
import time
import weakref
from collections import OrderedDict, deque
from concurrent.futures import Future, InvalidStateError
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import engine as engine_mod
from repro.engine import registry, tracing
from repro.engine.plan import (
    SolverPlan,
    fallback_chain,
    packed_plan_for,
    plan_for,
    resolved_pack_n_max,
)
from repro.engine.verify import verify_topk_host
from repro.kernels import blocks
from repro.runtime.chaos import ChaosError, ChaosFailure, ChaosMonkey
from repro.runtime.fault_tolerance import decorrelated_jitter

log = logging.getLogger("repro.engine.server")

#: Default matrix-size granule for shape buckets — the f32 sublane granule
#: the Pallas block clamp aligns to (``kernels/blocks.clamp_block``).
N_ALIGN = 8

#: Requests whose latencies ``stats()`` keeps for ``p50/p99_latency_ms``:
#: the most recent ones, so the record stays bounded for a server's life.
LATENCY_WINDOW = 4096

#: Host spans of the serving path (``eei.<name>`` in a profiler trace; see
#: ``engine/tracing.py`` and the "Tracing" section of
#: ``docs/ARCHITECTURE.md``), each counted as ``<name>_ns`` in ``stats()``.
SPANS = ("assemble", "copy_in", "launch", "device_wait", "fetch", "retire",
         "fallback", "session_update")


class ServerClosed(RuntimeError):
    """The server has been closed; the request was not (or will not be)
    served.  Late ``submit()`` calls get a future with this error already
    set rather than an exception at the call site, so producer loops that
    race ``close()`` observe a uniformly-resolved future either way."""


class QueueFull(RuntimeError):
    """``max_pending`` backpressure bound hit under ``pending_policy
    ='except'``."""


class VerifyFailed(RuntimeError):
    """A served result failed post-solve verification (non-finite entries,
    residual above tolerance, broken norm or bracket order).  Never reaches
    a caller while the fallback chain is enabled — it is the *cause* that
    routes a request down the chain; it only resolves a future when every
    fallback (including the eigh oracle) also failed."""


class DegradedResult(engine_mod.TopkResult):
    """A :class:`~repro.engine.engine.TopkResult` served by the fallback
    chain instead of the request's primary bucket program.

    Still a 2-tuple (``eigenvalues, vectors`` unpack as usual) so existing
    callers are oblivious; ``degraded`` is ``True`` (the base class carries
    ``False``) and ``fallback`` names the chain link that produced it
    (e.g. ``"eigh_oracle"``).  Quality is verified before resolution, so a
    degraded result is a *correct* result that took the slow path.
    """

    degraded = True

    def __new__(cls, eigenvalues, vectors, fallback: str = ""):
        self = super().__new__(cls, eigenvalues, vectors)
        self.fallback = fallback
        return self


def _is_transient(exc: BaseException) -> bool:
    """Whether a dispatch failure is worth retrying in place.

    Injected :class:`ChaosFailure` models the class: transient compile /
    launch / allocation errors.  Anything carrying a truthy ``transient``
    attribute opts in; everything else goes straight to split/fallback
    (retrying a deterministic error just burns the backoff budget).
    """
    return isinstance(exc, ChaosFailure) or bool(
        getattr(exc, "transient", False))


def _eigh_oracle(a: np.ndarray, k: int, largest: bool):
    """Terminal fallback: pure-numpy float64 LAPACK eigh on the host.

    No XLA, no device, no compile — the one link that cannot share a
    failure mode with the serving path.  Returns ``(lam (k,), vecs (k, n))``
    ascending at the requested extreme, rows as eigenvectors.
    """
    lam, v = np.linalg.eigh(np.asarray(a, dtype=np.float64))
    if largest:
        return lam[-k:], v[:, -k:].T
    return lam[:k], v[:, :k].T


def _bucket_n(n: int, align: int) -> int:
    """Matrix-size bucket: ``n`` rounded up to the block-grid granule."""
    return -(-n // align) * align


def make_eei_stream(
    requests: int, n: int, k: int, seed: int = 0, mixed: bool = False
) -> list:
    """Pre-generated request stream: ``[(a (n_i, n_i) np.float32, k_i), ...]``.

    Generated *outside* any timed region — host-side data synthesis used to
    run inside ``serve.py``'s timed loop and deflate reported solves/s.
    ``mixed`` samples ``n_i`` and ``k_i`` per request (the heterogeneous
    stream shape buckets exist for); otherwise every request is ``(n, k)``.
    """
    rng = np.random.default_rng(seed)
    sizes = sorted({max(8, n // 2), n, n + max(8, n // 2)}) if mixed else [n]
    stream = []
    for _ in range(requests):
        n_i = int(rng.choice(sizes))
        k_i = int(rng.integers(1, k + 1)) if mixed else k
        a = rng.standard_normal((n_i, n_i)).astype(np.float32)
        stream.append(((a + a.T) / 2, min(k_i, n_i)))
    return stream


class ShapeBucket(NamedTuple):
    """One padded program shape: every request executes through one of these."""

    b: int  # stack size (power of two)
    n: int  # matrix size (block-grid aligned)
    k: int  # top-k (power of two, <= n)
    largest: bool

    @classmethod
    def for_requests(cls, count: int, n: int, k: int, largest: bool,
                     n_align: int = N_ALIGN) -> "ShapeBucket":
        bn = _bucket_n(n, n_align)
        return cls(
            b=blocks.pow2_bucket(count),
            n=bn,
            k=min(blocks.pow2_bucket(k), bn),
            largest=bool(largest),
        )


class PackedBucket(NamedTuple):
    """One segment-packed program shape: ``b`` block-diagonal rows of width
    ``n``, each carrying up to ``s`` request segments, solved through the
    engine's ``packed_topk`` program kind (``k`` lanes per slot).

    A distinct class from :class:`ShapeBucket` on purpose: the two are
    tuples of different arity, so a packed key can never collide with a
    bucketed key in the :class:`ProgramCache` and ``isinstance`` routes the
    lowering (the packed program takes three operands, not one).
    """

    b: int  # stack size (power of two)
    n: int  # packed row width (block-grid aligned)
    s: int  # slot lanes per row (power of two)
    k: int  # per-slot window (power of two, >= every rider's k)
    largest: bool


def _bucket_label(bucket) -> str:
    """Human-readable stats key for either bucket type."""
    tail = "L" if bucket.largest else "S"
    if isinstance(bucket, PackedBucket):
        return f"pack:b{bucket.b}n{bucket.n}s{bucket.s}k{bucket.k}{tail}"
    return f"b{bucket.b}n{bucket.n}k{bucket.k}{tail}"


class _PendingProgram:
    """In-flight compile: later same-bucket getters wait on the event."""

    __slots__ = ("event", "program", "error")

    def __init__(self):
        self.event = threading.Event()
        self.program = None
        self.error = None


class ProgramCache:
    """Bucket -> AOT-compiled executable, with observable counters.

    Replaces the engine's implicit ``lru_cache``-plus-XLA-shape-cache
    behavior for serving: compiles are an explicit, countable event (tests
    and the serve log assert a mixed stream compiles at most once per
    distinct bucket), and entries hold the *compiled* executable — lookup
    on the hot path is one dict probe, no retracing.

    Thread-safe, and the lock is never held across a compile: a miss
    installs a per-key placeholder under the lock and compiles outside it,
    so concurrent gets for *other* buckets stay one-dict-probe fast while
    same-bucket racers wait on the placeholder's event (a *successful*
    compile happens at most once per bucket, so ``compiles == distinct
    buckets`` whenever every compile succeeds, and ``hits + misses``
    always equals the number of ``get()`` calls — a waiter counts as a
    hit).  A failed compile is re-raised to every waiter and evicted, so
    the next ``get()`` retries — that retry counts a fresh miss, so after
    transient compile failures ``compiles`` may exceed the distinct
    bucket count.
    The lock is a leaf in the server's lock order — nothing under it ever
    calls back into an ``EeiServer``.  One cache instance may be shared
    between servers (pass it as ``EeiServer(cache=...)``) to reuse
    compiles across server restarts or fuzzer iterations.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: "OrderedDict[tuple, object]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.compile_ns = 0  # perf_counter_ns time of the compiles

    @property
    def compiles(self) -> int:
        """Number of programs compiled (== misses: one compile per miss)."""
        return self.misses

    def __len__(self) -> int:
        return len(self._programs)

    def buckets(self) -> list:
        """The distinct buckets compiled so far (insertion order)."""
        with self._lock:
            return [key[0] for key in self._programs]

    def reset_counters(self) -> None:
        """Zero hit/miss counters, keeping compiled programs (benchmarks
        warm the cache, reset, then time a steady-state pass)."""
        with self._lock:
            self.hits = 0
            self.misses = 0
            self.compile_ns = 0

    def get(self, bucket, plan: SolverPlan, dtype, *,
            verify: bool = False) -> object:
        """The compiled program of ``bucket``; compiles it on a miss, in
        an ``eei.compile`` profiler annotation, timed into
        ``compile_ns``."""
        key = (bucket, plan, jnp.dtype(dtype).name, bool(verify))
        with self._lock:
            found = self._programs.get(key)
            if found is None:
                self.misses += 1  # this caller owns the compile
                entry = _PendingProgram()
                self._programs[key] = entry
            else:
                self.hits += 1
                if not isinstance(found, _PendingProgram):
                    return found
        if found is not None:
            # Same-bucket racer: wait for the owner's compile.
            found.event.wait()
            if found.error is not None:
                raise found.error
            return found.program
        t0 = time.perf_counter_ns()
        try:
            with jax.profiler.TraceAnnotation("eei.compile"):
                prog = self._compile(bucket, plan, dtype, verify)
        except BaseException as exc:
            entry.error = exc
            with self._lock:
                self.compile_ns += time.perf_counter_ns() - t0
                if self._programs.get(key) is entry:
                    del self._programs[key]  # next get() retries the compile
            entry.event.set()
            raise
        entry.program = prog
        with self._lock:
            self.compile_ns += time.perf_counter_ns() - t0
            self._programs[key] = prog
        entry.event.set()
        return prog

    @staticmethod
    def _compile(bucket, plan: SolverPlan, dtype, verify: bool) -> object:
        sds = jax.ShapeDtypeStruct((bucket.b, bucket.n, bucket.n),
                                   jnp.dtype(dtype))
        if isinstance(bucket, PackedBucket):
            fn = engine_mod.packed_topk_program(
                plan, bucket.k, bucket.largest, bool(verify))
            seg_sds = jax.ShapeDtypeStruct(
                (bucket.b, bucket.s), jnp.dtype(jnp.int32))
            return fn.lower(sds, seg_sds, seg_sds).compile()
        fn = engine_mod.topk_program(
            plan, bucket.k, bucket.largest, bool(verify))
        return fn.lower(sds).compile()


@dataclasses.dataclass(eq=False)  # identity equality: queue removal by object
class _Request:
    a: np.ndarray  # (n, n) symmetric, already cast to the server dtype
    n: int
    k: int
    largest: bool
    future: Future
    t_submit: float
    stack: Optional[int] = None  # sequence id of the last stack it rode


@dataclasses.dataclass
class _InflightStack:
    result: object  # TopkResult of device arrays, possibly still computing
    requests: list  # the _Requests whose slices ride in this stack
    bucket: object  # ShapeBucket, or PackedBucket for segment-packed stacks
    seq: int  # dispatch sequence id: the ``stack`` of its spans
    # Packed stacks only: per-request ``(row, slot, offset)`` parallel to
    # ``requests`` — retire slices each request's window out of its slot.
    layout: Optional[list] = None


@dataclasses.dataclass
class DispatchRecord:
    """One dispatched stack, as the conformance tests replay it: the exact
    padded input, the plan/bucket it compiled under, and the requests whose
    futures were resolved from its rows.  Recorded only when the server is
    constructed with ``record_dispatches=True``."""

    bucket: object  # ShapeBucket, or PackedBucket for packed dispatches
    plan: SolverPlan
    stack: np.ndarray  # the assembled (bucket.b, bucket.n, bucket.n) input
    requests: list  # [_Request, ...] in row (packed: layout) order
    #: ``time.monotonic()`` when the group left the admission queue —
    #: before assembly/compile, so ``t_dispatch - request.t_submit`` is the
    #: pure admission (linger) latency.
    t_dispatch: float = 0.0
    # Packed dispatches only: the (b, s) int32 segment layout operands and
    # the per-request (row, slot, offset) triples parallel to ``requests``.
    seg_off: Optional[np.ndarray] = None
    seg_len: Optional[np.ndarray] = None
    layout: Optional[list] = None


@dataclasses.dataclass(eq=False)
class _ServerSession:
    """Server-side record for one stateful spectral session.

    ``a_host`` is a float64 numpy mirror of the session matrix, updated on
    every submitted update *before* the fast path runs — it is what the
    degrade rung (and a fleet failover) rebuilds from, so it must never
    lag the stream.  ``lock`` serializes update execution and snapshot
    reads per session (the engine-side ``SpectralSession`` is not
    thread-safe)."""

    sid: str
    engine: object  # SolverEngine
    session: object  # repro.engine.session.SpectralSession
    a_host: np.ndarray
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)
    closed: bool = False


class EeiServer:
    """Concurrent continuous-batching server for heterogeneous EEI queries.

    ``submit(a, k, largest)`` enqueues one query over a single symmetric
    matrix and returns a ``concurrent.futures.Future`` resolving to a
    ``TopkResult`` of numpy arrays with the *request's* shapes
    (``(k,)`` eigenvalues, ``(k, n)`` vectors) — bucket padding never leaks.
    ``submit`` is safe from any number of producer threads in both modes.

    With ``linger_ms=None`` (default) dispatch is caller-driven: ``pump()``
    dispatches every coalesce group that fills a whole ``max_batch`` stack
    (``submit`` pumps automatically) and ``flush()`` drains everything,
    partial stacks included, and blocks until all futures resolve.

    With ``linger_ms`` set, a background admission thread dispatches full
    stacks immediately and partial stacks once their oldest request has
    waited ``linger_ms`` — sparse streams complete with no ``flush()`` at
    all — and a retire thread resolves futures off the producers' path.
    ``flush()`` is then a drain barrier, and ``close()`` (or using the
    server as a context manager) drains and joins the threads.

    ``max_pending`` bounds the number of queued-but-undispatched requests:
    ``pending_policy='block'`` makes ``submit`` wait for space (in
    caller-driven mode it drains inline instead, which keeps single-threaded
    callers live), ``'except'`` makes it raise :class:`QueueFull`.

    ``plan`` pins one :class:`SolverPlan` for every bucket; by default each
    bucket gets ``plan_for((b, n, n), k=..., mesh=mesh)`` so small-n buckets
    may route to ``eigh`` while large-n buckets take the kernelized EEI
    pipeline — and, when a ``mesh`` with a multi-device data axis is given,
    large stacks route to the ``sharded`` backend (pow2 stack buckets round
    up to the mesh batch axis).

    **Fault tolerance** (on by default): ``verify=True`` appends the
    engine's ``verify`` stage to every bucket program, so each stack row is
    checked (finiteness, residual, norm, bracket order) before its future
    resolves.  A dispatch failure retries transients up to ``max_retries``
    with exponential backoff, then bisection-splits the stack to isolate
    the poisoned request(s); an isolated failing request (and any row
    failing verification) escalates through the per-request fallback chain
    (``plan.fallback_chain()`` + a pure-numpy eigh oracle) and resolves as
    a :class:`DegradedResult` — garbage never reaches a caller, and no
    future is ever stranded.  ``fallback=False`` restores fail-fast
    semantics (the group's futures get the error).  ``chaos`` arms
    deterministic fault injection (:class:`~repro.runtime.chaos
    .ChaosMonkey`) for the conformance suite and ``serve.py --chaos`` soak
    runs.
    """

    def __init__(
        self,
        plan: Optional[SolverPlan] = None,
        *,
        max_batch: int = 64,
        max_inflight: int = 2,
        n_align: int = N_ALIGN,
        dtype=jnp.float32,
        linger_ms: Optional[float] = None,
        max_pending: int = 0,
        pending_policy: str = "block",
        mesh: Optional[jax.sharding.Mesh] = None,
        cache: Optional[ProgramCache] = None,
        record_dispatches: bool = False,
        pack: str = "never",
        pack_row_n: int = 64,
        pack_k: int = 8,
        verify: bool = True,
        fallback: bool = True,
        max_retries: int = 2,
        retry_backoff_s: float = 0.005,
        retry_backoff_cap_s: float = 1.0,
        retry_jitter_seed: Optional[int] = None,
        chaos: Optional[ChaosMonkey] = None,
        adaptive_linger: bool = True,
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
        if linger_ms is not None and linger_ms < 0:
            raise ValueError(f"linger_ms must be >= 0, got {linger_ms}")
        if max_pending < 0:
            raise ValueError(f"max_pending must be >= 0, got {max_pending}")
        if pending_policy not in ("block", "except"):
            raise ValueError(
                f"pending_policy must be 'block' or 'except', "
                f"got {pending_policy!r}")
        self._plan = plan
        self._mesh = mesh
        # Stack buckets are powers of two, so a non-pow2 bound would round
        # *up* past the operator's memory/latency limit — floor it instead
        # (a max_batch of 48 serves stacks of at most 32).
        self.max_batch = 1 << (max_batch.bit_length() - 1)
        self.max_inflight = max_inflight
        self.n_align = n_align
        self.dtype = jnp.dtype(dtype)
        self.linger_ms = linger_ms
        self.max_pending = max_pending
        self.pending_policy = pending_policy
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        if pack not in ("auto", "never", "always"):
            raise ValueError(
                f"pack must be 'auto', 'never' or 'always', got {pack!r}")
        if pack_row_n < n_align:
            raise ValueError(
                f"pack_row_n must be >= n_align ({n_align}), got {pack_row_n}")
        if pack_k < 1:
            raise ValueError(f"pack_k must be >= 1, got {pack_k}")
        self.pack = pack
        self.pack_row_n = _bucket_n(pack_row_n, n_align)
        self.pack_k = int(pack_k)
        # Slot lanes per packed row: bounded by the smallest footprint a
        # segment can occupy (one align granule).
        self._pack_max_slots = max(1, self.pack_row_n // n_align)
        self.cache = cache if cache is not None else ProgramCache()
        self.record_dispatches = record_dispatches
        self.dispatch_log: "list[DispatchRecord]" = []
        self.verify = bool(verify)
        self.fallback = bool(fallback)
        self.max_retries = int(max_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.retry_backoff_cap_s = float(retry_backoff_cap_s)
        # Decorrelated-jitter retry schedule: deterministic exponential
        # backoff makes stacks that failed *together* (same transient device
        # hiccup) retry together and re-collide every attempt.  The jitter
        # generator is seedable so tests can replay a schedule; draws are
        # serialized under the server lock (numpy Generators are not
        # thread-safe) and recorded in ``retry_delays_s`` for inspection.
        self._retry_rng = np.random.default_rng(retry_jitter_seed)
        self.retry_delays_s: list = []
        self.chaos = chaos

        # One re-entrant lock guards queues, in-flight state and counters;
        # one condition variable carries every wakeup (new work, linger
        # deadline, capacity, drain progress) — notify_all on any state
        # change, so no waiter class can miss its wakeup.  Re-entrant so a
        # future callback that re-enters submit() from a server thread
        # cannot self-deadlock.
        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)
        # Admission is bucketed at submit time: coalesce key -> FIFO deque.
        # Keys are independent, so a partial group in one key never blocks a
        # full stack forming in another, and group take-off is O(group)
        # instead of a full-queue scan.
        self._queues: "OrderedDict[tuple, deque]" = OrderedDict()
        self._inflight: "deque[_InflightStack]" = deque()
        # Every admitted-but-unresolved caller future -> its submit time.
        # Maintained by the universal done-callback, so it is correct
        # across every resolution path (retire, fallback, fail, cancel).
        # ``close(timeout=...)`` returns its keys when a drain wedges, and
        # the fleet's health probe reads the oldest age for its deadline.
        self._unresolved: "dict[Future, float]" = {}
        self._pending = 0  # queued, not yet popped for dispatch
        self._dispatching = 0  # groups popped but not yet in-flight/failed
        self._retiring = 0  # stacks popped by the retire thread, syncing
        self._draining = 0  # flush() barriers forcing partial dispatch
        self._closed = False
        self._admission_done = False
        self.requests_submitted = 0
        self.requests_completed = 0
        self.requests_failed = 0
        self.requests_rejected = 0  # late submits after close()
        self.requests_cancelled = 0  # caller-cancelled while still pending
        self.stacks_dispatched = 0
        self.packed_stacks_dispatched = 0
        self.packed_requests_completed = 0
        # Pad-waste accounting: every grid cell of a stack (b * n^2) versus
        # the cells carrying real request data (sum of the group's n_i^2).
        # The complement is what guard diagonals and batch-repeat padding
        # burn.  Cells are counted once per *successfully retired* stack —
        # at launch time they would double-count under retries, bisection
        # splits and fleet redispatch (the same request's cells landing
        # again with every relaunch), which is exactly the over-reporting
        # bug this accounting replaces; see ``stats()`` for the contract.
        self.grid_cells_total = 0
        self.grid_cells_real = 0
        self._pad_cells_by_bucket: dict = {}  # bucket -> [real, total]
        self.latencies_ms: deque = deque(maxlen=LATENCY_WINDOW)
        # Span counters (``<span>_ns``), the requests'
        # summed queue wait (dispatch minus submit, first dispatch only)
        # and the Lanczos steps of real rows; flat ints in stats().
        self._spans = tracing.SpanCounters(
            SPANS, extra=("queue_wait_ns", "lanczos_steps"))
        self._stack_ids = itertools.count()
        # Robustness counters (see stats()): verification failures routed
        # to the fallback chain, transient retries, bisection splits of
        # failed stacks, requests resolved degraded, and which fallback
        # link resolved them.
        self.verify_failed = 0
        self.retries = 0
        self.stack_splits = 0
        self.requests_degraded = 0
        self.fallbacks_by_plan: dict = {}  # chain link name -> resolutions

        # Adaptive linger: per-coalesce-key EWMA of inter-arrival gaps.
        # A key that runs hot (small gaps) shrinks its *effective* linger
        # toward the time its stack would plausibly still fill, so a hot
        # stream's partial stacks stop waiting out the full base timeout
        # when the stream hiccups.  Shrink-only: ``linger_ms`` stays the
        # upper bound, so cold/sparse keys keep the configured window.
        # Rate state survives reset_stats() (it describes the *stream*,
        # not a measurement pass); the trim counter does not.
        self.adaptive_linger = bool(adaptive_linger)
        self._key_rate: dict = {}  # key -> [ewma_gap_s, last_t, gap_samples]
        self.linger_trims = 0

        # Stateful spectral sessions (engine/session.py behind submit()'s
        # style of Future API).  Threaded mode executes updates on a lazy
        # dedicated session thread (serial per server, so per-session order
        # is dispatch order); caller-driven mode runs them inline.
        self._sessions: dict = {}  # sid -> _ServerSession
        self._session_ids = itertools.count()
        self._session_ops: "deque[tuple]" = deque()
        self._session_busy = 0
        self._session_thread: Optional[threading.Thread] = None
        self.sessions_opened = 0
        self.session_updates = 0
        self.session_fast_updates = 0
        self.session_full_resolves = 0
        self.session_degraded = 0

        # Snapshot the mode: _threaded must not flip if a caller mutates
        # linger_ms later (the linger *value* is re-read each admission
        # round; the thread topology is fixed at construction).
        self._threaded_mode = linger_ms is not None
        self._admission_thread: Optional[threading.Thread] = None
        self._retire_thread: Optional[threading.Thread] = None
        if self._threaded:
            self._admission_thread = threading.Thread(
                target=self._admission_main, name="eei-admission", daemon=True)
            self._retire_thread = threading.Thread(
                target=self._retire_main, name="eei-retire", daemon=True)
            self._admission_thread.start()
            self._retire_thread.start()

    @property
    def _threaded(self) -> bool:
        return self._threaded_mode

    # -- admission ---------------------------------------------------------

    def submit(self, a, k: int, largest: bool = True) -> Future:
        """Admit one ``(n, n)`` top-k query; returns its completion future.

        Thread-safe.  After ``close()`` the returned future already carries
        a :class:`ServerClosed` error.  With ``max_pending`` set, blocks or
        raises :class:`QueueFull` per ``pending_policy``.
        """
        a = np.asarray(a, dtype=self.dtype)
        if a.ndim != 2 or a.shape[0] != a.shape[1]:
            raise ValueError(f"expected one (n, n) matrix, got {a.shape}")
        n = a.shape[0]
        if k < 1 or k > n:
            raise ValueError(f"k={k} out of range for n={n}")
        req = _Request(a=a, n=n, k=int(k), largest=bool(largest),
                       future=Future(), t_submit=time.monotonic())
        with self._cv:
            if self._closed:
                return self._reject_locked(req)
            if self.max_pending and self._pending >= self.max_pending:
                if self.pending_policy == "except":
                    raise QueueFull(
                        f"{self._pending} requests pending "
                        f"(max_pending={self.max_pending})")
                if not self._threaded:
                    # Caller-driven mode has no admission thread to make
                    # space — drain inline so single-threaded producers
                    # stay live instead of self-deadlocking.
                    self.flush()
                else:
                    while self._pending >= self.max_pending:
                        self._cv.wait()
                        if self._closed:
                            return self._reject_locked(req)
            key = self._coalesce_key(req)
            self._queues.setdefault(key, deque()).append(req)
            self._pending += 1
            self.requests_submitted += 1
            req.t_submit = time.monotonic()  # linger clock starts at enqueue
            self._unresolved[req.future] = req.t_submit
            self._observe_arrival_locked(key, req.t_submit)
            self._cv.notify_all()
        # Caller-side cancellation: while the request is still pending
        # (undispatched) a cancel() pulls it out of its coalesce group, so
        # an abandoned future never pads a stack.  Attached outside the
        # lock — an already-cancelled future runs the callback inline.
        # The callback holds only a weakref: Future never clears its done
        # callbacks, so a strong capture would pin every request's input
        # matrix alongside the result for as long as the caller retains
        # the future.
        req_ref = weakref.ref(req)
        req.future.add_done_callback(
            lambda fut, ref=req_ref: self._on_future_done(ref, fut))
        if not self._threaded:
            self.pump()
        return req.future

    def _on_future_done(self, req_ref, fut: Future) -> None:
        """Dequeue a request whose caller cancelled it while still pending.

        Runs for every resolved future (the done callback cannot filter):
        every resolution retires the future from the ``_unresolved`` map
        (the fleet's liveness probe and ``close(timeout=...)``'s return
        value read it); anything but a cancellation then returns.  A cancel
        that lands after the group was popped is left alone: its row is
        already part of an assembled stack (the device work is spent either
        way) and retirement tolerates the pre-resolved future.  A dead
        weakref means the request already left the pipeline entirely.
        """
        with self._cv:
            self._unresolved.pop(fut, None)
        if not fut.cancelled():
            return
        req = req_ref()
        if req is None:
            return
        with self._cv:
            q = self._queues.get(self._coalesce_key(req))
            if q is None or req not in q:
                return  # already dispatched (or being popped): rides along
            q.remove(req)
            if not q:
                del self._queues[self._coalesce_key(req)]
            self._pending -= 1
            self.requests_cancelled += 1
            self._cv.notify_all()  # backpressure space; linger re-evaluates

    def _reject_locked(self, req: _Request) -> Future:
        self.requests_rejected += 1
        req.future.set_exception(ServerClosed(
            "EeiServer is closed; request was rejected"))
        return req.future

    def _coalesce_key(self, req: _Request) -> tuple:
        # k is deliberately NOT part of the key: requests with different k
        # stack together (the program runs the group's max k rounded to a
        # power of two and each future slices its own k back out), so a
        # mixed-k stream coalesces into full stacks instead of fragmenting
        # into near-empty per-k groups.  Packable small-n requests coalesce
        # into ONE key per extreme regardless of their n — that collapse is
        # the core of the packing win on mixed streams: one queue fills a
        # stack as fast as all the per-n queues did together, so linger
        # windows stop fragmenting sparse traffic into padded singletons.
        if self._packable(req):
            return ("pack", req.largest)
        return (_bucket_n(req.n, self.n_align), req.largest)

    def _packable(self, req: _Request) -> bool:
        """Whether a request rides the segment-packed path.

        ``"auto"`` packs requests whose aligned footprint is at most the
        calibrated :func:`~repro.engine.plan.resolved_pack_n_max` (and at
        most half a row, so every packed row carries >= 2 segments);
        ``"always"`` relaxes to anything that fits a row.  ``k`` stays
        bounded by ``pack_k`` so the per-slot window never explodes the
        packed program's lane count.
        """
        if self.pack == "never" or req.k > self.pack_k:
            return False
        footprint = _bucket_n(req.n, self.n_align)
        if self.pack == "always":
            return footprint <= self.pack_row_n
        return footprint <= min(resolved_pack_n_max(), self.pack_row_n // 2)

    def _group_cap(self, key: tuple) -> int:
        """Requests forming a *full* stack for this coalesce key: packed
        keys fill ``max_batch`` rows of up to ``_pack_max_slots`` segments,
        bucketed keys one request per row."""
        if key[0] == "pack":
            return self.max_batch * self._pack_max_slots
        return self.max_batch

    def _pop_group_locked(self, key: tuple) -> list:
        q = self._queues[key]
        group = [q.popleft()
                 for _ in range(min(len(q), self._group_cap(key)))]
        if not q:
            del self._queues[key]
        self._pending -= len(group)
        self._cv.notify_all()  # space for backpressured producers
        return group

    def _pop_all_locked(self) -> list:
        """Every queued group (each at most ``max_batch``), queue emptied."""
        groups = []
        while self._queues:
            groups.append(self._pop_group_locked(next(iter(self._queues))))
        return groups

    # -- dispatch ----------------------------------------------------------

    def _guard_value(self, a: np.ndarray, largest: bool) -> float:
        """Diagonal guard for the padded block: strictly outside the
        spectrum, on the side away from the requested extreme."""
        radius = np.sum(np.abs(a), axis=1) - np.abs(np.diagonal(a))
        diag = np.diagonal(a)
        lo = float(np.min(diag - radius))
        hi = float(np.max(diag + radius))
        margin = max(1.0, 0.01 * (hi - lo))
        return lo - margin if largest else hi + margin

    def _assemble(self, group: list, bucket: ShapeBucket) -> np.ndarray:
        stack = np.zeros((bucket.b, bucket.n, bucket.n), dtype=self.dtype)
        for row, req in enumerate(group):
            stack[row, : req.n, : req.n] = req.a
            if req.n < bucket.n:
                guard = self._guard_value(req.a, req.largest)
                idx = np.arange(req.n, bucket.n)
                stack[row, idx, idx] = guard
        # Batch padding repeats the first padded row: real matrices, so the
        # program never sees degenerate all-zero inputs; sliced off below.
        stack[len(group):] = stack[0]
        return stack

    def _plan_bucket(self, group: list) -> tuple:
        bucket = ShapeBucket.for_requests(
            len(group), max(r.n for r in group), max(r.k for r in group),
            group[0].largest, n_align=self.n_align)
        # The plan is a pure function of the bucket (never of raw group
        # values), so one bucket can never compile under two plans.
        plan = self._plan
        if plan is None:
            plan = plan_for((bucket.b, bucket.n, bucket.n), k=bucket.k,
                            mesh=self._mesh)
        # The sharded backend needs the stack divisible by the mesh batch
        # axis (SolverEngine._run_chunk pads for the same reason) — round
        # the pow2 bucket up to the next multiple.
        mult = plan.batch_axis_size
        if bucket.b % mult:
            bucket = bucket._replace(b=bucket.b + (-bucket.b) % mult)
        return bucket, plan

    def _begin_stack(self, requests: list, t_disp: float) -> int:
        """Number a new stack and mark its riders with it; count the queue
        wait of riders on their first dispatch (a bisected half or a
        retried stack carries requests that already waited)."""
        seq = next(self._stack_ids)
        wait_s = 0.0
        for req in requests:
            if req.stack is None:
                wait_s += t_disp - req.t_submit
            req.stack = seq
        self._spans.add("queue_wait_ns", int(wait_s * 1e9))
        return seq

    def _launch(self, bucket, plan: SolverPlan, operands: tuple, seq: int):
        """Fetch the bucket program and launch it over ``operands`` (one
        stack for bucketed programs; stack + the two ``(b, s)`` segment
        arrays for packed ones), retrying *transient* failures (see
        :func:`_is_transient`) up to ``max_retries`` with
        decorrelated-jitter backoff.  Chaos compile/launch injection points
        live here — upstream of the retry logic, exactly like the real
        failures they model.  One ``eei.launch`` span of stack ``seq``
        covers it all, a compile included."""
        prev_delay = self.retry_backoff_s
        with self._spans.span("launch", stack=seq):
            for attempt in range(self.max_retries + 1):
                try:
                    if self.chaos is not None:
                        self.chaos.on_compile()
                    program = self.cache.get(
                        bucket, plan, self.dtype, verify=self.verify)
                    if self.chaos is not None:
                        self.chaos.on_launch()
                    return program(*operands)  # async: returns at once
                except Exception as exc:
                    if attempt >= self.max_retries or not _is_transient(exc):
                        raise
                    with self._cv:
                        self.retries += 1
                        prev_delay = decorrelated_jitter(
                            self._retry_rng, self.retry_backoff_s,
                            prev_delay, self.retry_backoff_cap_s)
                        self.retry_delays_s.append(prev_delay)
                        self._cv.notify_all()
                    log.warning(
                        "EEI dispatch retry %d/%d after transient: %s",
                        attempt + 1, self.max_retries, exc)
                    time.sleep(prev_delay)  # outside the lock

    def _dispatch(self, group: list) -> None:
        """Assemble, fetch the program, launch.  Never raises: any failure
        (planning, assembly, compile, launch) is retried / split / escalated
        down the fallback chain (``fallback=True``) or resolves the group's
        futures with the error — never stranding callers or killing a
        server thread.  Appends to ``_inflight`` under the lock.

        Groups whose every member is packable take the segment-packed path;
        packability is re-derived here (not trusted from the coalesce key)
        so bisection halves of a failed packed stack re-pack consistently.

        Pad-waste cell accounting deliberately does NOT happen here: cells
        are counted once per *successfully retired* stack (see
        ``_account_retired_locked``), because counting at launch double- or
        triple-counted every request that rode a retried, bisected or
        fleet-redispatched stack."""
        t_disp = time.monotonic()
        if group and all(self._packable(req) for req in group):
            self._dispatch_packed(group, t_disp)
            return
        seq = self._begin_stack(group, t_disp)
        try:
            bucket, plan = self._plan_bucket(group)
            with self._spans.span("assemble", stack=seq):
                stack = self._assemble(group, bucket)
            with self._spans.span("copy_in", stack=seq):
                operands = (jnp.asarray(stack),)
            result = self._launch(bucket, plan, operands, seq)
        except Exception as exc:  # compile/launch failure after retries:
            self._handle_group_failure(group, exc)  # split / fallback / fail
            return
        with self._cv:
            self._inflight.append(
                _InflightStack(result, list(group), bucket, seq))
            self.stacks_dispatched += 1
            if self.record_dispatches:
                self.dispatch_log.append(DispatchRecord(
                    bucket=bucket, plan=plan, stack=stack,
                    requests=list(group), t_dispatch=t_disp))
            self._cv.notify_all()

    def _packed_plan(self) -> SolverPlan:
        """The plan packed stacks compile under.

        A pinned ``plan=`` is honored when its method registers a
        ``packed_topk`` chain; otherwise (and in the default auto-plan
        mode) :func:`~repro.engine.plan.packed_plan_for` picks the
        calibrated eigh-vs-tridiag packed chain for ``pack_row_n``.
        """
        plan = self._plan
        if plan is not None:
            # Mirror engine._resolve_chain's packed_topk lookup (windowed
            # comp when the plan asks, falling back to the full comp).
            try:
                chain = registry.composition_for(
                    plan.method, plan.spectrum == "windowed").packed_topk
                if chain is None:
                    chain = registry.composition_for(
                        plan.method, False).packed_topk
            except KeyError:
                chain = None
            if chain is not None:
                return plan
            log.debug("pinned plan %s has no packed chain; using "
                      "packed_plan_for(%d)", plan, self.pack_row_n)
        return packed_plan_for(self.pack_row_n)

    def _dispatch_packed(self, group: list, t_disp: float = 0.0) -> None:
        """Segment-packed dispatch: first-fit pack the group's matrices
        into block-diagonal rows of width ``pack_row_n``, chunk the rows
        into stacks of at most ``max_batch``, and launch each chunk through
        the engine's ``packed_topk`` program — three operands: the packed
        stack plus the ``(b, s)`` segment-layout arrays.  Each chunk is its
        own in-flight stack, so a failure bisects/escalates only its own
        riders, exactly like a bucketed stack."""
        try:
            rows = blocks.pack_segments(
                [req.n for req in group], self.pack_row_n,
                self._pack_max_slots, align=self.n_align)
            plan = self._packed_plan()
        except Exception as exc:
            self._handle_group_failure(group, exc)
            return
        for start in range(0, len(rows), self.max_batch):
            chunk = rows[start:start + self.max_batch]
            sub = [group[i] for row in chunk for i, _, _ in row]
            seq = self._begin_stack(sub, t_disp)
            try:
                with self._spans.span("assemble", stack=seq):
                    bucket, stack, seg_off, seg_len, layout = \
                        self._assemble_packed(group, chunk)
                with self._spans.span("copy_in", stack=seq):
                    operands = (jnp.asarray(stack), jnp.asarray(seg_off),
                                jnp.asarray(seg_len))
                result = self._launch(bucket, plan, operands, seq)
            except Exception as exc:
                self._handle_group_failure(sub, exc)
                continue
            with self._cv:
                self._inflight.append(_InflightStack(
                    result, sub, bucket, seq, layout=layout))
                self.stacks_dispatched += 1
                self.packed_stacks_dispatched += 1
                if self.record_dispatches:
                    self.dispatch_log.append(DispatchRecord(
                        bucket=bucket, plan=plan, stack=stack,
                        requests=sub, seg_off=seg_off, seg_len=seg_len,
                        layout=layout, t_dispatch=t_disp))
                self._cv.notify_all()

    def _assemble_packed(self, group: list, chunk: list):
        """Build one packed stack from ``chunk``: a list of packed rows,
        each ``[(group_index, offset, length), ...]`` from
        :func:`~repro.kernels.blocks.pack_segments`.

        Returns ``(bucket, stack, seg_off, seg_len, layout)``; ``layout``
        holds per-request ``(row, slot, offset)`` triples in the same order
        the sub-requests ride the stack.  Diagonal cells outside every
        segment carry *spaced, distinct* guard values strictly outside the
        row's union Gershgorin interval, on the side away from the
        requested extreme: outside the union so no guard eigenvalue can
        enter any slot's window, distinct so the packed row's spectrum
        stays simple enough for the tridiagonal minor-determinant chain.
        Batch-pad rows repeat row 0's matrix with every ``seg_len`` zero —
        empty slots verify vacuously and retire nothing."""
        largest = group[0].largest
        b = blocks.pow2_bucket(len(chunk))
        s = blocks.pow2_bucket(max(len(row) for row in chunk))
        kmax = max(group[i].k for row in chunk for i, _, _ in row)
        n = self.pack_row_n
        bucket = PackedBucket(
            b=b, n=n, s=s, k=min(blocks.pow2_bucket(kmax), n),
            largest=largest)
        stack = np.zeros((b, n, n), dtype=self.dtype)
        seg_off = np.zeros((b, s), dtype=np.int32)
        seg_len = np.zeros((b, s), dtype=np.int32)
        layout = []
        for row, segs in enumerate(chunk):
            lo, hi = np.inf, -np.inf
            covered = np.zeros(n, dtype=bool)
            for slot, (i, off, length) in enumerate(segs):
                a = group[i].a
                stack[row, off:off + length, off:off + length] = a
                seg_off[row, slot] = off
                seg_len[row, slot] = length
                layout.append((row, slot, off))
                radius = np.sum(np.abs(a), axis=1) - np.abs(np.diagonal(a))
                diag = np.diagonal(a)
                lo = min(lo, float(np.min(diag - radius)))
                hi = max(hi, float(np.max(diag + radius)))
                covered[off:off + length] = True
            idx = np.where(~covered)[0]
            if idx.size:
                margin = max(1.0, 0.01 * (hi - lo))
                step = margin / idx.size
                if largest:
                    vals = lo - margin - step * np.arange(idx.size)
                else:
                    vals = hi + margin + step * np.arange(idx.size)
                stack[row, idx, idx] = vals
        # Batch padding repeats row 0's matrix (real data, never an all-zero
        # degenerate input) but with zero seg_len: nothing is selected,
        # verified or retired from a pad row.
        stack[len(chunk):] = stack[0]
        return bucket, stack, seg_off, seg_len, layout

    @staticmethod
    def _set(future: Future, *, result=None, error=None) -> bool:
        """Resolve a future, tolerating caller-side ``cancel()``: a
        cancelled future is already resolved, and raising out of a server
        thread here would poison every *other* request that thread owns."""
        try:
            if error is not None:
                future.set_exception(error)
            else:
                future.set_result(result)
            return True
        except InvalidStateError:
            return False

    def _fail(self, requests: list, exc: Exception) -> None:
        """Resolve a group's futures with the error — a failed dispatch
        must never strand callers blocked on ``future.result()``.
        Counters update before the futures resolve (see ``_retire``)."""
        log.error("EEI stack dispatch failed for %d request(s): %s",
                  len(requests), exc)
        with self._cv:
            self.requests_failed += len(requests)
            self._cv.notify_all()
        for req in requests:
            self._set(req.future, error=exc)

    def _handle_group_failure(self, group: list, exc: Exception) -> None:
        """A stack failed (dispatch error after retries, or a device-side
        error at retire).  With the fallback chain enabled, bisection-split
        multi-request groups to isolate the poisoned request(s) — each half
        re-dispatches through the normal path, so healthy halves ride a
        fresh stack — and escalate isolated requests down the per-request
        chain.  With ``fallback=False``, fail-fast as before."""
        if not self.fallback:
            self._fail(group, exc)
            return
        if len(group) > 1:
            log.warning("EEI stack of %d failed (%s); bisecting",
                        len(group), exc)
            with self._cv:
                self.stack_splits += 1
                self._cv.notify_all()
            mid = len(group) // 2
            self._dispatch(group[:mid])
            self._dispatch(group[mid:])
            return
        self._fallback_request(group[0], exc)

    def _fallback_request(self, req: _Request, cause: Exception) -> None:
        """Escalate one isolated request down the fallback chain.

        Each link re-solves the request's *unpadded* matrix and is
        host-verified before it may resolve the future; the terminal link
        is the pure-numpy eigh oracle.  Resolves the future with a
        :class:`DegradedResult` on the first verified link, or with the
        original cause if every link fails (non-finite input, say)."""
        with self._spans.span("fallback", stack=req.stack):
            self._walk_fallback_chain(req, cause)

    def _walk_fallback_chain(self, req: _Request, cause: Exception) -> None:
        a = req.a
        for name, plan in fallback_chain():
            try:
                res = engine_mod.SolverEngine(plan).topk(
                    jnp.asarray(a), req.k, req.largest)
                lam = np.asarray(res.eigenvalues)
                vec = np.asarray(res.vectors)
            except Exception as exc:
                log.debug("fallback %s raised for n=%d k=%d: %s",
                          name, req.n, req.k, exc)
                continue
            if not bool(verify_topk_host(a, lam, vec).ok):
                log.debug("fallback %s failed verification (n=%d k=%d)",
                          name, req.n, req.k)
                continue
            self._resolve_degraded(req, lam, vec, name, cause)
            return
        try:
            lam, vec = _eigh_oracle(a, req.k, req.largest)
        except Exception as exc:
            self._fail([req], exc)
            return
        if not bool(verify_topk_host(a, lam, vec).ok):
            # Even LAPACK could not produce a verifiable answer — the input
            # itself is poisoned (non-finite, say).  Surface the original
            # cause, not garbage.
            self._fail([req], cause)
            return
        self._resolve_degraded(req, lam, vec, "eigh_oracle", cause)

    def _resolve_degraded(self, req: _Request, lam: np.ndarray,
                          vec: np.ndarray, name: str,
                          cause: Exception) -> None:
        log.info("EEI request (n=%d, k=%d) resolved degraded via %s "
                 "(cause: %s)", req.n, req.k, name, cause)
        t_done = time.monotonic()
        with self._cv:
            self.requests_degraded += 1
            self.requests_completed += 1
            self.fallbacks_by_plan[name] = \
                self.fallbacks_by_plan.get(name, 0) + 1
            self.latencies_ms.append((t_done - req.t_submit) * 1e3)
            self._cv.notify_all()
        self._set(req.future, result=DegradedResult(
            lam.astype(self.dtype), vec.astype(self.dtype), fallback=name))

    def _retire(self, inflight: _InflightStack) -> None:
        """Block on one stack, verify, and resolve its requests' futures.

        Called with the lock held in caller-driven mode (the device sync is
        the caller's own flush) and without it from the retire thread (the
        sync must not block producers).

        With ``verify`` on, the program returned ``(TopkResult,
        VerifyFlags)``: rows whose flags fail — or whose host slices carry
        non-finite values (the chaos NaN injection lands on the host copy,
        exactly like a corrupted transfer would) — escalate down the
        per-request fallback chain instead of resolving with garbage.  A
        device-side failure at the sync point re-enters the split/fallback
        path like a dispatch failure."""
        seq = inflight.seq
        flags_ok = steps = None
        try:
            with self._spans.span("device_wait", stack=seq):
                jax.block_until_ready(inflight.result)
            with self._spans.span("fetch", stack=seq):
                result = inflight.result
                if self.verify:
                    result, flags = result
                    flags_ok = np.asarray(flags.ok)
                    if flags.steps is not None:
                        steps = np.asarray(flags.steps)
                lam = np.asarray(result.eigenvalues)
                vec = np.asarray(result.vectors)
        except Exception as exc:  # device-side failure surfaces here
            self._handle_group_failure(inflight.requests, exc)
            return
        with self._spans.span("retire", stack=seq):
            escalate = self._retire_rows(inflight, flags_ok, steps, lam, vec)
        for req in escalate:
            cause = VerifyFailed(
                f"result for (n={req.n}, k={req.k}) failed verification")
            if self.fallback:
                self._fallback_request(req, cause)
            else:
                self._fail([req], cause)

    def _retire_rows(self, inflight: _InflightStack, flags_ok, steps,
                     lam: np.ndarray, vec: np.ndarray) -> list:
        """Slice each request's answer out of a fetched stack, account for
        the stack and resolve the futures of the rows that passed; returns
        the requests to escalate."""
        if self.chaos is not None:
            vec = self.chaos.on_result(vec)
            self.chaos.on_retire_sleep()
        t_done = time.monotonic()
        results = []
        escalate = []
        if inflight.layout is not None:
            # Packed stack: lam (b, S, K), vec (b, S, K, N), flags (b, S).
            # Each request slices its k pairs out of its own slot's window
            # and its n columns out of its segment's offset.
            for req, (row, slot, off) in zip(inflight.requests,
                                             inflight.layout):
                if req.largest:
                    lam_r = lam[row, slot, -req.k:]
                    vec_r = vec[row, slot, -req.k:, off:off + req.n]
                else:
                    lam_r = lam[row, slot, : req.k]
                    vec_r = vec[row, slot, : req.k, off:off + req.n]
                if flags_ok is not None and not (
                        bool(flags_ok[row, slot])
                        and np.all(np.isfinite(lam_r))
                        and np.all(np.isfinite(vec_r))):
                    escalate.append(req)
                    continue
                results.append((req, engine_mod.TopkResult(lam_r, vec_r)))
        else:
            for row, req in enumerate(inflight.requests):
                # The program returns `bucket.k` ascending pairs at the
                # requested extreme.  Guards were placed on the far side of
                # the spectrum, so the request's k pairs are the window's
                # own extreme end: the *last* k for largest, the *first* k
                # for smallest.
                if req.largest:
                    lam_r = lam[row, -req.k:]
                    vec_r = vec[row, -req.k:, : req.n]
                else:
                    lam_r = lam[row, : req.k]
                    vec_r = vec[row, : req.k, : req.n]
                if flags_ok is not None and not (
                        bool(flags_ok[row])
                        and np.all(np.isfinite(lam_r))
                        and np.all(np.isfinite(vec_r))):
                    escalate.append(req)
                    continue
                results.append((req, engine_mod.TopkResult(lam_r, vec_r)))
        if steps is not None:
            # Rows past the requests are batch padding: their steps are
            # device work no request asked for.
            self._spans.add("lanczos_steps",
                            int(np.sum(steps[:len(inflight.requests)])))
        # Counters update BEFORE futures resolve: a caller woken by
        # future.result() may read stats() immediately and must see this
        # stack's requests already accounted for.
        with self._cv:
            self.latencies_ms.extend(
                (t_done - req.t_submit) * 1e3 for req, _ in results)
            self.requests_completed += len(results)
            if inflight.layout is not None:
                self.packed_requests_completed += len(results)
            self.verify_failed += len(escalate)
            self._account_retired_locked(inflight)
            self._cv.notify_all()
        for req, res in results:
            self._set(req.future, result=res)
        return escalate

    def _account_retired_locked(self, inflight: _InflightStack) -> None:
        """Pad-waste cell accounting, exactly once per *successfully
        retired* stack.

        Counting used to happen at dispatch, which over-reported whenever
        the same request rode more than one launch: in-place transient
        retries were safe (one count per successful launch), but a stack
        that failed at its retire sync re-entered ``_dispatch`` through
        bisection, and a fleet failover redispatched a dead replica's
        requests through a second replica's dispatch path — every such
        request's cells were counted two or more times, so chaos runs
        reported inflated ``grid_cells_*`` relative to the identical clean
        stream.  Counting at retire makes the counters mean "cells the
        serving programs actually computed and handed back": each retired
        stack counts once, and requests that escalate to the per-request
        fallback chain (whose solves are unpadded) add nothing."""
        bucket = inflight.bucket
        total = bucket.b * bucket.n * bucket.n
        real = sum(req.n * req.n for req in inflight.requests)
        self.grid_cells_total += total
        self.grid_cells_real += real
        cells = self._pad_cells_by_bucket.setdefault(bucket, [0, 0])
        cells[0] += real
        cells[1] += total

    def _make_room_locked(self) -> None:
        """Caller-driven mode: retire the oldest stack(s) until a launch
        keeps at most ``max_inflight`` stacks of device buffers live."""
        while len(self._inflight) >= self.max_inflight:
            self._retire(self._inflight.popleft())

    # -- background threads ------------------------------------------------

    #: Inter-arrival gaps a key must show before its EWMA can shrink the
    #: linger window — below this the estimate is noise, and sparse tests /
    #: streams that submit a handful of requests keep the configured linger.
    _LINGER_MIN_SAMPLES = 4
    #: EWMA smoothing factor for inter-arrival gaps.
    _LINGER_EWMA_ALPHA = 0.3
    #: Effective linger = ``_LINGER_GAP_FACTOR * ewma_gap * remaining
    #: slots`` — the time the stack would plausibly still take to fill if
    #: the stream kept its observed rate, with 2x slack for jitter.
    _LINGER_GAP_FACTOR = 2.0

    def _observe_arrival_locked(self, key: tuple, t: float) -> None:
        rate = self._key_rate.get(key)
        if rate is None:
            self._key_rate[key] = [0.0, t, 0]
            return
        # Clamp each observed gap to the base linger window: a longer gap
        # means the key went *idle* (its previous stack long since
        # dispatched), not that the arrival rate is that slow — and an
        # estimate at/above the base can never trim, so one clamped idle
        # gap also instantly heals a stale-hot estimate after a burst.
        gap = max(t - rate[1], 0.0)
        if self.linger_ms:
            gap = min(gap, self.linger_ms / 1e3)
        alpha = self._LINGER_EWMA_ALPHA
        rate[0] = gap if rate[2] == 0 else (1 - alpha) * rate[0] + alpha * gap
        rate[1] = t
        rate[2] += 1

    def _effective_linger_locked(self, key: tuple, qlen: int,
                                 base_s: float) -> float:
        """Per-key linger window: the base, shrunk for hot keys.

        A hot key's partial stack only ever waits about as long as the
        stack would take to *fill* at the observed arrival rate — once the
        stream pauses longer than that, waiting out the rest of the base
        window buys nothing (the stack was not going to fill) and just
        adds latency.  Shrink-only, so the base stays an upper bound and
        an idle/sparse key is untouched.
        """
        if not self.adaptive_linger:
            return base_s
        rate = self._key_rate.get(key)
        if rate is None or rate[2] < self._LINGER_MIN_SAMPLES:
            return base_s
        remaining = max(self._group_cap(key) - qlen, 1)
        return min(base_s, self._LINGER_GAP_FACTOR * rate[0] * remaining)

    def _ready_key_locked(self, now: float):
        """Dispatchable coalesce key, or ``(None, deadline)`` where
        ``deadline`` is the next linger expiry (``None`` if no queue).

        Among ready keys (full, linger-expired, or force-drained) the one
        with the *oldest head request* wins — FIFO across keys.  Picking
        the first ready key in insertion order would let a continuously
        full key starve another key's expired partial group indefinitely;
        with oldest-head order the starved key's fixed, aging head
        eventually outranks the hot key's ever-renewing one, so the
        linger bound stays a real latency bound.

        Each key lingers under its own *effective* window (see
        :meth:`_effective_linger_locked`): hot keys trim toward their
        observed fill time, and a dispatch that happened strictly earlier
        than the base window because of the trim counts in
        ``linger_trims``.
        """
        force = self._closed or self._draining > 0
        linger_s = (self.linger_ms or 0.0) / 1e3
        best_key = best_t = deadline = None
        best_trim = False
        for key, q in self._queues.items():
            head_t = q[0].t_submit
            eff = self._effective_linger_locked(key, len(q), linger_s)
            expiry = head_t + eff
            full = len(q) >= self._group_cap(key)
            if full or force or now >= expiry:
                if best_t is None or head_t < best_t:
                    best_key, best_t = key, head_t
                    best_trim = (not full and not force
                                 and eff < linger_s
                                 and now < head_t + linger_s)
            elif best_key is None:
                deadline = expiry if deadline is None else \
                    min(deadline, expiry)
        if best_key is not None and best_trim:
            self.linger_trims += 1
        return best_key, (None if best_key is not None else deadline)

    def _admission_loop(self) -> None:
        while True:
            if self.chaos is not None:
                # Injected at the loop top, before any group is held, so a
                # chaos crash kills the thread between stacks — the restart
                # wrapper in _admission_main resumes with nothing stranded.
                self.chaos.on_thread("admission")
            with self._cv:
                while True:
                    key, deadline = self._ready_key_locked(time.monotonic())
                    if key is not None:
                        group = self._pop_group_locked(key)
                        self._dispatching += 1
                        break
                    if self._closed and not self._queues:
                        return
                    timeout = None
                    if deadline is not None:
                        timeout = max(deadline - time.monotonic(), 0.0) + 1e-4
                    self._cv.wait(timeout)
            try:
                with self._cv:
                    # Capacity gate: at most max_inflight stacks of device
                    # buffers outstanding (on device + being retired).
                    while len(self._inflight) + self._retiring >= \
                            self.max_inflight:
                        if not self._retire_thread.is_alive():
                            # Retirement is permanently gone (bounded
                            # restarts exhausted): capacity will never
                            # free — fail the held group instead of
                            # waiting forever (close() must not hang).
                            raise ServerClosed(
                                "retire thread died; cannot dispatch")
                        self._cv.wait(timeout=0.1)
                # Outside the lock: assembly, cache lookup (possibly a
                # multi-second compile) and the async launch never block
                # producers or the retire thread.
                self._dispatch(group)
            except BaseException as exc:
                # _dispatch absorbs Exceptions, so this is a BaseException
                # (crash) or the capacity gate's dead-retirement escape.
                # The popped group is in no queue anymore — resolve its
                # futures before the crash handler takes over (_set
                # tolerates rows _dispatch already resolved).
                self._fail(group, ServerClosed(
                    f"admission thread crashed: {exc!r}"))
                raise
            finally:
                with self._cv:
                    self._dispatching -= 1
                    self._cv.notify_all()

    def _admission_main(self) -> None:
        try:
            while True:
                try:
                    self._admission_loop()
                    return
                except ChaosError:
                    # Injected crash, fired between stacks: nothing is
                    # held, so restart in place.  Bounded by the injection
                    # schedule itself (deterministic, finite in tests) —
                    # real crashes below keep their fail-everything path.
                    log.warning(
                        "EEI admission thread: injected crash; restarting")
        except BaseException as exc:  # never die silently: fail the queue
            log.exception("EEI admission thread crashed")
            with self._cv:
                # Nothing will drain the queues anymore — close so later
                # submits are rejected instead of silently stranded.
                self._closed = True
                groups = self._pop_all_locked()
            for group in groups:
                self._fail(group, ServerClosed(
                    f"admission thread crashed: {exc!r}"))
        finally:
            with self._cv:
                self._admission_done = True
                self._cv.notify_all()

    def _retire_loop(self) -> None:
        while True:
            if self.chaos is not None:
                # Before any stack is popped: a chaos crash here exercises
                # the bounded-restart machinery with nothing stranded.
                self.chaos.on_thread("retire")
            with self._cv:
                while not self._inflight:
                    if self._admission_done and not self._dispatching:
                        return
                    self._cv.wait()
                stack = self._inflight.popleft()
                self._retiring += 1
                self._cv.notify_all()
            try:
                self._retire(stack)
            except BaseException as exc:
                # _retire absorbs Exceptions; a BaseException here would
                # otherwise strand the popped stack (it is no longer in
                # _inflight, so the crash handler cannot see it).
                self._fail(stack.requests, ServerClosed(
                    f"retire thread crashed: {exc!r}"))
                raise
            finally:
                with self._cv:
                    self._retiring -= 1
                    self._cv.notify_all()

    def _retire_main(self) -> None:
        # A handful of restarts: a crash drains + fails the stacks it held,
        # then keeps retiring whatever the admission thread still launches,
        # so one bad stack never strands later ones.  Persistent crashing
        # gives up after the bounded retries (close() joins regardless).
        # Injected ChaosError crashes fire between stacks (nothing held),
        # so they restart in place without closing the server or burning
        # the real-crash budget — the schedule is deterministic and finite.
        crashes = 0
        while crashes < 8:
            try:
                self._retire_loop()
                return
            except ChaosError:
                log.warning("EEI retire thread: injected crash; restarting")
            except BaseException as exc:
                crashes += 1
                log.exception("EEI retire thread crashed")
                with self._cv:
                    self._closed = True  # stop admitting: retirement is sick
                    stacks = list(self._inflight)
                    self._inflight.clear()
                    self._cv.notify_all()
                for stack in stacks:
                    self._fail(stack.requests, ServerClosed(
                        f"retire thread crashed: {exc!r}"))

    # -- stateful sessions -------------------------------------------------

    def _session_plan(self, n: int, k: int) -> SolverPlan:
        plan = self._plan
        if plan is None:
            bn = _bucket_n(n, self.n_align)
            plan = plan_for((1, bn, bn), k=k, mesh=self._mesh)
        return plan

    def open_session(self, a, k: int, largest: bool = True,
                     config=None) -> str:
        """Open a stateful spectral session over one ``(n, n)`` matrix.

        Seeds the session with a full solve (synchronous — it is a setup
        call, like the compile it triggers) and returns a session id for
        :meth:`submit_update` / :meth:`session_result` /
        :meth:`close_session`.
        """
        from repro.engine import session as session_mod

        a = np.asarray(a, dtype=np.float64)
        if a.ndim != 2 or a.shape[0] != a.shape[1]:
            raise ValueError(f"expected one (n, n) matrix, got {a.shape}")
        with self._cv:
            if self._closed:
                raise ServerClosed("EeiServer is closed")
        eng = engine_mod.SolverEngine(self._session_plan(a.shape[0], k))
        session = eng.open_session(a, k, largest, config=config)
        with self._cv:
            if self._closed:
                raise ServerClosed("EeiServer is closed")
            sid = f"s{next(self._session_ids)}"
            self._sessions[sid] = _ServerSession(
                sid=sid, engine=eng, session=session, a_host=a.copy())
            self.sessions_opened += 1
            if self._threaded and self._session_thread is None:
                self._session_thread = threading.Thread(
                    target=self._session_main, name="eei-session",
                    daemon=True)
                self._session_thread.start()
            self._cv.notify_all()
        return sid

    def _get_session(self, session_id: str) -> _ServerSession:
        with self._cv:
            rec = self._sessions.get(session_id)
        if rec is None:
            raise KeyError(f"no session {session_id!r}")
        return rec

    def submit_update(self, session_id: str, u, sign: int = 1) -> Future:
        """Apply ``A <- A + sign * u u^T`` to a session; returns a future
        resolving to the refreshed top-k window (request-shaped numpy
        arrays, like :meth:`submit`).

        Per-session updates resolve in submission order.  A fast-path
        failure degrades to a full solve from the host mirror (the PR-7
        terminal rung) instead of erroring — the future then resolves
        with a :class:`DegradedResult`.
        """
        rec = self._get_session(session_id)
        u = np.asarray(u, dtype=self.dtype)
        fut = Future()
        with self._cv:
            if self._closed or rec.closed:
                fut.set_exception(ServerClosed(
                    f"session {session_id!r} is closed"))
                return fut
            if self._threaded:
                self._session_ops.append((rec, u, int(sign), fut))
                self._cv.notify_all()
                return fut
        self._session_exec_update(rec, u, int(sign), fut)
        return fut

    def session_result(self, session_id: str):
        """Snapshot of a session's current top-k window (numpy arrays)."""
        rec = self._get_session(session_id)
        with rec.lock:
            res = rec.session.result()
            return engine_mod.TopkResult(
                np.asarray(res.eigenvalues), np.asarray(res.vectors))

    def session_stats(self, session_id: str) -> dict:
        rec = self._get_session(session_id)
        with rec.lock:
            return rec.session.stats()

    def close_session(self, session_id: str) -> None:
        """Drop a session.  Updates already queued for it resolve with
        :class:`ServerClosed`; in-execution updates finish normally."""
        with self._cv:
            rec = self._sessions.pop(session_id, None)
            if rec is not None:
                rec.closed = True
                self._cv.notify_all()

    def _session_exec_update(self, rec: _ServerSession, u: np.ndarray,
                             sign: int, fut: Future) -> None:
        """Run one update under the session lock; never raises.

        Request errors (bad shape / non-finite input) fail the future
        directly — degrading cannot fix a malformed request.  Anything
        else (a broken fast path, a sick backend) degrades to a host
        full solve from the mirror, so the session survives every fault
        the PR-7 chain survives."""
        with self._spans.span("session_update", session=rec.sid):
            self._session_apply_update(rec, u, sign, fut)

    def _session_apply_update(self, rec: _ServerSession, u: np.ndarray,
                              sign: int, fut: Future) -> None:
        from repro.engine import session as session_mod

        u64 = np.asarray(u, dtype=np.float64)
        with rec.lock:
            try:
                before = rec.session.full_resolves
                res = rec.engine.update(
                    rec.session, session_mod.Rank1Update(u, sign))
                rec.a_host += sign * np.outer(u64, u64)
            except ValueError as exc:  # malformed request: fail, don't mask
                with self._cv:
                    self.requests_failed += 1
                    self._cv.notify_all()
                self._set(fut, error=exc)
                return
            except Exception as exc:
                self._session_degrade(rec, u64, sign, fut, exc)
                return
            lam = np.asarray(res.eigenvalues)
            vec = np.asarray(res.vectors)
            full = rec.session.full_resolves > before
        with self._cv:
            self.session_updates += 1
            if full:
                self.session_full_resolves += 1
            else:
                self.session_fast_updates += 1
            self._cv.notify_all()
        self._set(fut, result=engine_mod.TopkResult(lam, vec))

    def _session_degrade(self, rec: _ServerSession, u64: np.ndarray,
                         sign: int, fut: Future, cause: Exception) -> None:
        """Terminal session rung: host eigh full solve from the mirror.

        Called with ``rec.lock`` held, mirror NOT yet updated for this
        ``u`` (the engine commits state only on success, so the mirror
        and the session agree at entry)."""
        from repro.engine import session as session_mod

        log.warning("session %s update degrading to host solve (%s)",
                    rec.sid, cause)
        if not self.fallback:
            with self._cv:
                self.requests_failed += 1
                self._cv.notify_all()
            self._set(fut, error=cause)
            return
        try:
            rec.a_host += sign * np.outer(u64, u64)
            session_mod.host_reseed(rec.session, rec.a_host)
            res = rec.session.result()
            lam = np.asarray(res.eigenvalues)
            vec = np.asarray(res.vectors)
        except Exception:
            with self._cv:
                self.requests_failed += 1
                self._cv.notify_all()
            self._set(fut, error=cause)
            return
        with self._cv:
            self.session_updates += 1
            self.session_full_resolves += 1
            self.session_degraded += 1
            self._cv.notify_all()
        self._set(fut, result=DegradedResult(
            lam, vec, fallback="host_reseed"))

    def _session_main(self) -> None:
        """Session executor (threaded mode): drains ``_session_ops``
        serially.  Exits once the server is closed and the queue is empty
        (queued ops still execute on a draining close — ``close()`` fails
        them first when ``drain=False``)."""
        while True:
            with self._cv:
                while not self._session_ops:
                    if self._closed:
                        return
                    self._cv.wait()
                rec, u, sign, fut = self._session_ops.popleft()
                self._session_busy += 1
                self._cv.notify_all()
            try:
                if rec.closed:
                    self._set(fut, error=ServerClosed(
                        f"session {rec.sid!r} is closed"))
                else:
                    self._session_exec_update(rec, u, sign, fut)
            finally:
                with self._cv:
                    self._session_busy -= 1
                    self._cv.notify_all()

    # -- draining ----------------------------------------------------------

    def pump(self) -> None:
        """Dispatch every coalesce group that fills a whole stack.

        Partial groups keep accumulating (so the stream batches instead of
        degenerating to per-request programs), but only within their own
        key — a partial group never delays a full stack of another shape.
        In threaded mode this is just a wakeup for the admission thread.
        """
        if self._threaded:
            with self._cv:
                self._cv.notify_all()
            return
        with self._cv:
            for key in [k for k, q in self._queues.items()
                        if len(q) >= self._group_cap(k)]:
                while len(self._queues.get(key, ())) >= self._group_cap(key):
                    self._make_room_locked()
                    self._dispatch(self._pop_group_locked(key))

    def flush(self) -> None:
        """Drain: dispatch all queued requests (partial stacks too) and
        block until every in-flight stack has retired.

        Idempotent and re-callable: a second ``flush()`` (including one
        racing the first from another thread) finds nothing left and
        returns immediately.  In threaded mode this is a barrier — the
        admission thread does the dispatching; the caller just waits.
        """
        if self._threaded:
            with self._cv:
                self._draining += 1
                self._cv.notify_all()
                try:
                    while (self._queues or self._dispatching
                           or self._inflight or self._retiring
                           or self._session_ops or self._session_busy):
                        if self._admission_done and not (
                                self._retire_thread
                                and self._retire_thread.is_alive()):
                            break  # threads gone; nothing will drain more
                        self._cv.wait(timeout=0.1)
                finally:
                    self._draining -= 1
                    self._cv.notify_all()
            return
        with self._cv:
            while self._queues:
                self._make_room_locked()
                key = next(iter(self._queues))
                self._dispatch(self._pop_group_locked(key))
            while self._inflight:
                self._retire(self._inflight.popleft())

    def close(self, drain: bool = True, timeout: Optional[float] = None
              ) -> list:
        """Shut the server down.  Idempotent.  Returns the list of caller
        futures still unresolved when it returns — **empty on a clean
        drain**.

        ``drain=True`` (default) dispatches everything still queued and
        blocks until every future has resolved; ``drain=False`` resolves
        queued requests' futures with :class:`ServerClosed` instead (all
        futures still resolve — never stranded), but always retires stacks
        already on device.  After ``close()``, ``submit()`` returns futures
        with :class:`ServerClosed` already set.

        In threaded mode ``timeout`` bounds the *whole* call: the two
        thread joins share one deadline, and if the drain is wedged (a
        stuck device sync, a straggler-injected retire) ``close`` returns
        the still-unresolved futures instead of hanging or raising — the
        caller (a fleet failing over, an operator shutting down) decides
        whether to redispatch or abandon them.  The background threads are
        daemons; a later resolution still flows to the futures normally.
        """
        with self._cv:
            first = not self._closed
            self._closed = True
            groups = self._pop_all_locked() if first and not drain else []
            session_ops = []
            if first and not drain:
                session_ops = list(self._session_ops)
                self._session_ops.clear()
            self._cv.notify_all()
        for group in groups:
            self._fail(group, ServerClosed(
                "EeiServer closed before this request was dispatched"))
        for _rec, _u, _sign, fut in session_ops:
            self._set(fut, error=ServerClosed(
                "EeiServer closed before this update was applied"))
        if self._threaded:
            deadline = None if timeout is None else \
                time.monotonic() + timeout
            threads = [self._admission_thread, self._retire_thread]
            if self._session_thread is not None:
                threads.append(self._session_thread)
            for thread in threads:
                left = None if deadline is None else \
                    max(deadline - time.monotonic(), 0.0)
                thread.join(left)
            if (self._admission_thread.is_alive()
                    or self._retire_thread.is_alive()):
                with self._cv:
                    stranded = list(self._unresolved)
                log.error(
                    "EeiServer.close(): drain did not finish within %ss; "
                    "%d future(s) still unresolved", timeout, len(stranded))
                return stranded
        elif first:
            if drain:
                self.flush()
            else:
                # Stacks already on device must still retire — their device
                # work is spent either way, and their futures must resolve.
                with self._cv:
                    while self._inflight:
                        self._retire(self._inflight.popleft())
        return []

    def __enter__(self) -> "EeiServer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    # -- replica introspection (read by EeiFleet) --------------------------

    def alive(self) -> bool:
        """Whether this server can still make progress on admitted work.
        A closed server, or a threaded server whose service threads died
        (bounded restarts exhausted), is not alive."""
        with self._cv:
            if self._closed:
                return False
        if self._threaded:
            return (self._admission_thread.is_alive()
                    and self._retire_thread.is_alive())
        return True

    def unresolved_futures(self) -> list:
        """Snapshot of every admitted caller future not yet resolved, in
        submit order.  The fleet walks this on replica death to redispatch
        exactly the work the replica still owed."""
        with self._cv:
            return sorted(self._unresolved, key=self._unresolved.get)

    def oldest_unresolved_age_s(self, now: Optional[float] = None
                                ) -> Optional[float]:
        """Age of the oldest admitted-but-unresolved request (None when
        idle) — the fleet's deadline probe: a hung replica accepts work
        and never answers, so *only* this age keeps growing."""
        with self._cv:
            if not self._unresolved:
                return None
            oldest = min(self._unresolved.values())
        return (time.monotonic() if now is None else now) - oldest

    def pending_manifest(self) -> list:
        """Queued-but-undispatched requests: ``[{n, k, largest, age_s}]``."""
        now = time.monotonic()
        with self._cv:
            return [
                {"n": r.n, "k": r.k, "largest": r.largest,
                 "age_s": now - r.t_submit}
                for q in self._queues.values() for r in q
            ]

    def inflight_manifest(self) -> list:
        """Dispatched-but-unretired stacks: ``[{bucket, rows, oldest_age_s}]``."""
        now = time.monotonic()
        with self._cv:
            return [
                {"bucket": f"b{s.bucket.b}n{s.bucket.n}k{s.bucket.k}"
                           + ("L" if s.bucket.largest else "S"),
                 "rows": len(s.requests),
                 "oldest_age_s": now - min(r.t_submit for r in s.requests)}
                for s in self._inflight
            ]

    # -- observability -----------------------------------------------------

    def reset_stats(self) -> None:
        """Zero request/stack/latency counters and the cache's hit counter,
        keeping compiled programs — benchmarks warm the cache with one pass,
        reset, then time a steady-state pass (compiles then stay 0)."""
        with self._cv:
            self.requests_submitted = 0
            self.requests_completed = 0
            self.requests_failed = 0
            self.requests_rejected = 0
            self.requests_cancelled = 0
            self.stacks_dispatched = 0
            self.packed_stacks_dispatched = 0
            self.packed_requests_completed = 0
            self.grid_cells_total = 0
            self.grid_cells_real = 0
            self._pad_cells_by_bucket = {}
            self.latencies_ms = deque(maxlen=LATENCY_WINDOW)
            self.dispatch_log = []
            self.verify_failed = 0
            self.retries = 0
            self.retry_delays_s = []
            self.stack_splits = 0
            self.requests_degraded = 0
            self.fallbacks_by_plan = {}
            # _key_rate survives: it describes the stream's arrival shape,
            # which a stats reset (a benchmark pass boundary) doesn't change.
            self.linger_trims = 0
            self.sessions_opened = 0
            self.session_updates = 0
            self.session_fast_updates = 0
            self.session_full_resolves = 0
            self.session_degraded = 0
        self._spans.reset()
        self.cache.reset_counters()

    def stats(self) -> dict:
        """Counter snapshot.

        Pad-waste semantics (``grid_cells_*``, ``pad_waste_*``): cells are
        counted exactly once per *successfully retired* stack — a stack
        that is retried, bisection-split or redispatched by a fleet
        failover contributes once, when (and only when) a program's result
        is actually handed back; requests served by the per-request
        fallback chain contribute nothing.  ``stacks_dispatched`` counts
        *launches* instead, so ``stacks_dispatched`` can exceed the number
        of retired stacks under chaos while the cell counters match the
        equivalent clean run.  ``pad_waste_bucketed_frac`` /
        ``pad_waste_packed_frac`` split the waste by dispatch path.  The
        packed fraction counts every cell of the ``(b, n, n)`` packed
        rows, which charges a block-diagonal row quadratically for its
        structural off-block zeros — so the packed fraction sits *above*
        the bucketed one by construction, pricing the trade packing
        makes: more guard cells per launch in exchange for far fewer
        launches and compiled programs on fragmented ragged traffic.
        Compare each fraction against its own history, not against the
        other path's.

        ``p50_latency_ms`` / ``p99_latency_ms`` are submit-to-resolve
        percentiles over the last ``LATENCY_WINDOW`` requests.  Every span
        of ``SPANS`` adds ``<span>_ns``, and ``program_compile_ns`` is the
        cache's compile time (inside ``launch_ns``);
        ``queue_wait_ns`` sums each request's wait from submit to its first
        dispatch, and ``lanczos_steps`` the Lanczos steps of every real row
        of a Krylov stack.  All are flat ints, zeroed by ``reset_stats()``.
        """
        with self._cv:
            lat = sorted(self.latencies_ms)
            packed_real = packed_total = buck_real = buck_total = 0
            for bk, (real, total) in self._pad_cells_by_bucket.items():
                if isinstance(bk, PackedBucket):
                    packed_real += real
                    packed_total += total
                else:
                    buck_real += real
                    buck_total += total
            snap = {
                "requests_submitted": self.requests_submitted,
                "requests_completed": self.requests_completed,
                "requests_failed": self.requests_failed,
                "requests_rejected": self.requests_rejected,
                "requests_cancelled": self.requests_cancelled,
                "requests_pending": self._pending,
                "requests_unresolved": len(self._unresolved),
                "stacks_dispatched": self.stacks_dispatched,
                "packed_stacks_dispatched": self.packed_stacks_dispatched,
                "packed_requests_completed": self.packed_requests_completed,
                "grid_cells_total": self.grid_cells_total,
                "grid_cells_real": self.grid_cells_real,
                "pad_waste_frac": (
                    1.0 - self.grid_cells_real / self.grid_cells_total
                    if self.grid_cells_total else 0.0),
                "pad_waste_bucketed_frac": (
                    1.0 - buck_real / buck_total if buck_total else 0.0),
                "pad_waste_packed_frac": (
                    1.0 - packed_real / packed_total
                    if packed_total else 0.0),
                "pad_waste_by_bucket": {
                    _bucket_label(bk):
                        round(1.0 - real / total, 6) if total else 0.0
                    for bk, (real, total)
                    in sorted(self._pad_cells_by_bucket.items(),
                              key=lambda kv: _bucket_label(kv[0]))},
                "verify_failed": self.verify_failed,
                "retries": self.retries,
                "stack_splits": self.stack_splits,
                "requests_degraded": self.requests_degraded,
                "fallbacks_by_plan": dict(self.fallbacks_by_plan),
                "linger_trims": self.linger_trims,
                "sessions_open": len(self._sessions),
                "sessions_opened": self.sessions_opened,
                "session_updates": self.session_updates,
                "session_fast_updates": self.session_fast_updates,
                "session_full_resolves": self.session_full_resolves,
                "session_degraded": self.session_degraded,
                "chaos_injected": (
                    self.chaos.counts() if self.chaos is not None else {}),
            }

        def pct(p):
            if not lat:
                return 0.0
            return lat[min(len(lat) - 1, int(p / 100.0 * len(lat)))]

        snap.update({
            "program_compiles": self.cache.compiles,
            "program_compile_ns": self.cache.compile_ns,
            "program_hits": self.cache.hits,
            "distinct_buckets": len(self.cache),
            "p50_latency_ms": pct(50),
            "p99_latency_ms": pct(99),
            **self._spans.snapshot(),
        })
        return snap
