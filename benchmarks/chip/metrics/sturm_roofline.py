"""The ``sturm`` Pallas kernel's share of its roofline, in percent.

Useful work per kernel call, from the problem alone: the real requests in
the stack (``requests_completed / stacks_dispatched`` over the window; the
stack's padding rows do not count), times the ``k`` eigenvalues of the
window, times the float32 bisection iterations, times the Krylov band
length the configuration states.  Each step of the Sturm recurrence
``q = d - x - e^2 / q`` is 4 operations (a square, a division, two
subtractions).  Bytes: the band's diagonal and off-diagonal, once per real
request.  The least time is the larger of operations over the chip's peak
rate and bytes over its memory bandwidth; the share is that least time over
the kernel's device time in the traced stretch.  Padded lanes and the
loop's structure are not counted.  The compute bound sets it for every
configuration here (see ``bound``).

The compute peak is the chip's published one (``peaks.json``: bf16 on the
matrix unit).  The recurrence runs in float32 on the vector unit, whose
peak the vendor does not publish; against that unit's own peak the share
would read higher.  So this reads the kernel's distance from the chip's
compute roofline, a lower bound of its share of the unit it runs on.
"""

from harness import trace as trace_mod

#: Device ops of the kernel, by name in the trace.
PATTERN = r"^sturm_padded(\.\d+)?$"
OPS_PER_STEP = 4
F32_BYTES = 4


def work(rows: float, k: int, iters: int, band: int) -> tuple:
    """Operations and bytes the kernel is asked for in one call."""
    ops = rows * k * iters * band * OPS_PER_STEP
    moved = rows * 2 * band * F32_BYTES
    return ops, moved


def bound(ops: float, moved: float, peaks: dict) -> tuple:
    """``(least seconds, "compute" | "memory")``."""
    t_ops = ops / peaks["flops_per_s"]
    t_mem = moved / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def read(ctx):
    if ctx.trace is None:
        return None
    kernel = trace_mod.kernel_time(ctx.trace, PATTERN)
    stacks = ctx.record.counters.get("stacks_dispatched", 0)
    if kernel is None or kernel["seconds"] <= 0 or stacks <= 0:
        return None
    rows = ctx.record.counters["requests_completed"] / stacks
    cfg = ctx.config
    ops, moved = work(rows, cfg["k"], cfg["bisect_iters"], cfg["krylov_m"])
    least, _ = bound(ops, moved, ctx.peaks)
    return 100.0 * kernel["count"] * least / kernel["seconds"]
