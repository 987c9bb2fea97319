"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b \
        --shape train_4k --steps 100 --mesh 1x1 [--reduced] [--eigenpre]

Wires together: config registry -> model -> sharded programs -> synthetic
data pipeline (prefetched) -> Supervisor (checkpoint/restart, straggler
watchdog) -> training loop.  ``--reduced`` runs the smoke-size config (CPU
container); full-size runs are the same code path on a real mesh.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import SHAPES, ShapeConfig
from repro.configs.registry import ARCHS, get_config, reduced_config
from repro.data import PrefetchIterator, make_synthetic
from repro.launch import mesh as mesh_lib
from repro.models.lm import LanguageModel
from repro.optim import AdamW, EigenPre
from repro.runtime import Supervisor, SupervisorConfig, StragglerWatchdog
from repro.runtime.compile_cache import enable_compile_cache
from repro.train import TrainState, build_programs

log = logging.getLogger("repro.train")


def parse_mesh(spec: str):
    parts = [int(p) for p in spec.split("x")]
    if len(parts) == 2:
        return mesh_lib.make_local_mesh(*parts)
    if len(parts) == 3:
        return jax.make_mesh(tuple(parts), ("pod", "data", "model"))
    raise ValueError(f"bad mesh spec {spec!r}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--shape", choices=sorted(SHAPES), default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--mesh", default="1x1", help="DxM or PxDxM")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-size config (CPU)")
    ap.add_argument("--batch", type=int, default=0,
                    help="override global batch")
    ap.add_argument("--seq", type=int, default=0, help="override seq len")
    ap.add_argument("--eigenpre", action="store_true",
                    help="EEI spectral preconditioner (the paper in the loop)")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()
    logging.basicConfig(level=logging.INFO)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    shape = SHAPES[args.shape]
    if args.batch or args.seq:
        shape = dataclasses.replace(
            shape,
            global_batch=args.batch or shape.global_batch,
            seq_len=args.seq or shape.seq_len,
        )
    if args.reduced and not (args.batch and args.seq):
        shape = ShapeConfig(shape.name, args.seq or 64, args.batch or 4,
                            shape.kind)

    mesh = parse_mesh(args.mesh)
    model = LanguageModel(cfg)
    optimizer = EigenPre() if args.eigenpre else AdamW()
    compute_dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    log.info("arch=%s params=%.3fM mesh=%s", cfg.name, model.n_params() / 1e6,
             mesh.devices.shape)

    with mesh:
        programs = build_programs(model, mesh, optimizer=optimizer,
                                  compute_dtype=compute_dtype,
                                  microbatch=args.microbatch or None)
        params = jax.jit(
            model.init, out_shardings=programs.state_shardings.params
        )(jax.random.PRNGKey(args.seed))
        opt_state = jax.jit(
            optimizer.init, out_shardings=programs.state_shardings.opt_state
        )(params)
        state = TrainState(params, opt_state, jnp.zeros((), jnp.int32))

        manager = CheckpointManager(
            f"{args.ckpt_dir}/{cfg.name}", keep=3)
        supervisor = Supervisor(
            manager, SupervisorConfig(checkpoint_every=args.ckpt_every))
        supervisor.install_signal_handlers()
        start_step = 0
        if args.resume and manager.latest_step() is not None:
            state, extra = manager.restore(
                state, shardings=programs.state_shardings)
            start_step = extra.get("data_step", manager.latest_step())
            log.info("resumed at step %s", start_step)

        source = make_synthetic(cfg, shape, seed=args.seed)
        data = PrefetchIterator(source, start_step=start_step)
        watchdog = StragglerWatchdog()

        def put(batch):
            return {
                k: jax.device_put(v, programs.batch_shardings[k])
                for k, v in batch.items()
            }

        def step_fn(state, batch):
            return programs.train_step(state, put(batch))

        t_start = time.monotonic()

        def on_metrics(step, metrics, dt):
            watchdog.observe(step, dt)
            if step % args.log_every == 0:
                loss = float(np.asarray(metrics["loss"]))
                gn = float(np.asarray(metrics.get("grad_norm", 0.0)))
                log.info("step %5d loss %.4f |g| %.3f %.2fs/step",
                         step, loss, gn, dt)

        state = supervisor.run(state, data, step_fn, args.steps,
                               state_shardings=programs.state_shardings,
                               on_metrics=on_metrics)
        data.close()
        log.info("done: %d steps in %.1fs (stragglers flagged: %d)",
                 args.steps, time.monotonic() - t_start, watchdog.events)
    return state


if __name__ == "__main__":
    main()
