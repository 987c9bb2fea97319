#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

From the root of a checkout.  The cell, its configuration, traffic mix,
driver and per-layer metric readers are looked up by name from
``BENCHMARK.json`` (see ``harness/spec.py`` and ``README.md``).

A run: checks that JAX sees a TPU with the chips the cell asks for (else
it exits non-zero and prints no result); makes the inputs on the device
from ``--seed``; builds the server and warms every program the traffic
will use (set-up, timed from process start); drives the traffic for
``--seconds``; reads the device's peak memory; frees the server; and
checks a seeded sample of the window's answers against the float64
reference.  With ``--trace 1`` a stretch of the window is profiled and the
cell's per-layer metrics are reported instead of its end-to-end ones.

The last line on standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"check"}``; the line before it holds diagnostics (compiles in set-up and
in the window, counters).  The compared numbers and their limits are also
the last lines on standard error.

JAX's persistent compilation cache is kept at ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"

if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

from harness import device, reference, spec  # noqa: E402


def configure_jax() -> None:
    """Point JAX's persistent compilation cache (and the program's, which
    reads the same variable) at the checkout, and cache every program."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def import_program() -> None:
    """Put the system under test (``<checkout>/src/repro``) on the path."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"no program at {src / 'repro'}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric reader may read."""

    metric: str
    config: dict
    traffic: dict
    record: object  # harness.client.WindowRecord
    trace: object  # harness.trace.reduce(...) summary, or None
    peaks: dict


def _finite(x):
    if isinstance(x, float) and not math.isfinite(x):
        return sys.float_info.max if x > 0 else -sys.float_info.max
    return x


def run_cell(bench: spec.Benchmark, cell: spec.Cell, seed: int,
             seconds: float, trace: bool, t_process: float, *,
             require_tpu: bool = True, control: bool = False) -> tuple:
    """One run of ``cell``.  Returns ``(result, info)``: the result line's
    object and the diagnostics.  ``require_tpu=False`` skips the look for
    a chip (the harness's own tests); ``control=True`` checks the bfloat16
    control's answers in place of the program's."""
    import jax

    from harness import client
    from harness import trace as trace_mod

    devices = (device.require_tpu(cell.chips) if require_tpu
               else jax.devices()[:cell.chips])
    desc = device.describe(jax.devices())
    peaks = device.peaks(desc["kind"]) if require_tpu else {}
    driver = bench.driver(cell)
    with client.CompileMeter() as setup_meter:
        drv = driver.Driver(cell, seed)
    t_window = time.perf_counter()
    setup_s = t_window - t_process
    stretch = trace_mod.Stretch(trace, cell.traffic["trace"]["lead_s"],
                                cell.traffic["trace"]["length_s"], cell.chips)
    with client.CompileMeter() as window_meter:
        record = drv.window(seconds, stretch)
    memory_peak = device.memory_peak_bytes(devices)
    pool_peak = drv.pool_peak_bytes
    t_release = time.perf_counter()
    samples = drv.release()
    del drv
    t_reduce = time.perf_counter()

    metrics, breakdown = {}, None
    if not trace:
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" \
                else record.end_to_end[m["name"]]
            metrics[m["name"]] = {"value": _finite(value), "unit": m["unit"]}
    else:
        summary = trace_mod.reduce(stretch.events)
        for m in cell.per_layer:
            ctx = LayerContext(m["name"], cell.config, cell.traffic, record,
                               summary, peaks)
            value = bench.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": _finite(value),
                                      "unit": m["unit"]}
        desc = dict(desc, busy_s=summary["busy_s"],
                    window_s=summary["window_s"])
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}

    t_check = time.perf_counter()
    limits = {**cell.config["limits"], **cell.traffic.get("limits", {})}
    checked = reference.check(samples, limits, control=control)
    t_done = time.perf_counter()
    result = {
        "correct": checked["correct"],
        "attempted": record.attempted,
        "failed": record.failed,
        "metrics": metrics,
        "device": dict(desc, memory_peak_bytes=memory_peak),
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {k: {"value": _finite(v["value"]), "limit": v["limit"]}
                       for k, v in checked["numbers"].items()}
    info = {
        "workload": cell.name, "seed": seed, "seconds": seconds,
        "setup_s": setup_s, "window_s": record.t_end - record.t_start,
        "release_s": t_reduce - t_release, "reduce_s": t_check - t_reduce,
        "check_s": t_done - t_check,
        "setup_compiles": setup_meter.count,
        "setup_compile_s": setup_meter.seconds,
        "window_compiles": window_meter.count,
        "window_compile_s": window_meter.seconds,
        "end_to_end": {k: _finite(v) for k, v in record.end_to_end.items()},
        "counters": record.counters, "samples": len(samples),
        "pool_peak_bytes": pool_peak,
    }
    return result, info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = spec.Benchmark.load(ROOT / "BENCHMARK.json", HERE)
        cell = bench.cell(args.workload)
        import_program()
        configure_jax()
        result, info = run_cell(bench, cell, args.seed, args.seconds,
                                bool(args.trace), T_PROCESS)
    except device.NoAccelerator as exc:
        print(f"run.py: {exc}", file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps({"info": info}), flush=True)
    for name, num in result["check"].items():
        print(f"check {name}: {num['value']!r} (limit {num['limit']!r})",
              file=sys.stderr)
    print(f"check correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
