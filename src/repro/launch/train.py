"""Training launcher — end-to-end driver (deliverable (b)).

CPU-scale run of any smoke config with full substrate (data pipeline, AdamW,
checkpointing/restart, deterministic resume):

  PYTHONPATH=src python -m repro.launch.train --arch gemma2-9b --steps 200
  PYTHONPATH=src python -m repro.launch.train --trace /tmp/train_trace.json

On a real multi-host TPU deployment, the same trainer runs under
``jax.distributed.initialize()`` with the production mesh from launch/mesh.py
and the sharding rules from dist/sharding.py (see launch/dryrun.py for the
exact pjit wiring proven by the 512-device dry-run).

``--trace OUT.json`` attaches a ``repro.obs`` Tracer + MetricsRegistry to
the Trainer (per-step spans, step-time histogram, cross-pod wire-byte
counters on pod meshes) and exports a Perfetto-loadable Chrome trace.
Verbosity is the ``REPRO_LOG`` env knob.
"""

from __future__ import annotations

import argparse

from repro.configs import get_smoke_config
from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import MetricsRegistry, Tracer, get_logger
from repro.obs.metrics import time_s
from repro.optim import AdamWConfig, warmup_cosine
from repro.train import Trainer, TrainerConfig

log = get_logger("train")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--inject-failure-at", type=int, default=None,
                    help="raise after N steps to demo checkpoint/restart")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient accumulation factor")
    ap.add_argument("--mesh-shape", default=None,
                    help="comma-separated mesh over (pod,data,model) axes, "
                         "e.g. '2,2' — leading axis is the pod axis")
    ap.add_argument("--compress-pods", action="store_true",
                    help="int8 error-feedback cross-pod gradient reduction "
                         "(residual is checkpointed train-step state)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="export a Chrome trace (Perfetto) of the run, with "
                         "the step-time/wire-byte metrics snapshot embedded")
    args = ap.parse_args()

    mesh_shape = (tuple(int(x) for x in args.mesh_shape.split(","))
                  if args.mesh_shape else None)
    cfg = get_smoke_config(args.arch)
    log.info(f"arch={cfg.name} params={cfg.param_count()/1e6:.2f}M "
             f"mesh={mesh_shape} compress={args.compress_pods} "
             f"microbatches={args.microbatches}")
    tracer, metrics = ((Tracer(), MetricsRegistry()) if args.trace
                       else (None, None))
    trainer = Trainer(
        cfg,
        AdamWConfig(learning_rate=warmup_cosine(args.lr, 10, args.steps),
                    weight_decay=0.1),
        DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                   global_batch=args.batch),
        TrainerConfig(total_steps=args.steps, checkpoint_every=args.ckpt_every,
                      checkpoint_dir=args.ckpt_dir, log_every=10,
                      microbatches=args.microbatches, mesh_shape=mesh_shape,
                      compress_pods=args.compress_pods),
        tracer=tracer, metrics=metrics,
    )
    (_, _, history), dt = time_s(trainer.run,
                                 inject_failure_at=args.inject_failure_at)
    for step, loss in history:
        log.info(f"step {step:5d} loss {loss:.4f}")
    tok_s = args.steps * args.batch * args.seq / dt
    log.info(f"done: {dt:.1f}s, {tok_s:.0f} tok/s on CPU")
    if args.trace:
        tracer.export(args.trace, metrics=metrics)
        log.info(f"trace: {args.trace} ({len(tracer)} events, "
                 f"{len(metrics)} metrics)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
