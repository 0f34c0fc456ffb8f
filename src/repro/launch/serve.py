"""Serving launcher — batched-request demo with the HEFT_RT front end.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --requests 12
  PYTHONPATH=src python -m repro.launch.serve --paged      # continuous batching
  PYTHONPATH=src python -m repro.launch.serve --sharded    # mesh-backed fleet
  PYTHONPATH=src python -m repro.launch.serve --trace /tmp/serve_trace.json
  PYTHONPATH=src python -m repro.launch.serve --full --num-layers 16 \
      --paged --fused-scheduler --max-len 1024   # published widths (TPU)

``--paged`` serves through the block-paged KV pool (``serve/paging.py``):
requests are HEFT_RT-mapped and then *admitted into the running batch* at
each decode tick (``--max-batch`` slots, ``--page-size``-token pages;
``--num-pages`` below full occupancy exercises admission queueing), and
request 0 is checked against the model's full forward pass: each served
token's logit within ``ORACLE_TOL_ULPS`` of the reference's top logit
(see ``check_against_reference``).  See docs/serving.md for the design.

Default mode builds a small heterogeneous "fleet" of replicas of a
smoke-config model (speed factors emulate mixed pods).  ``--sharded`` carves
the local device pool into mesh slices instead (``--mesh-shapes 1x1,2x1,2x2``
with enough devices, e.g. under ``XLA_FLAGS=--xla_force_host_platform_
device_count=8``): each replica is a real ``repro.dist`` substrate and the
HEFT_RT front end maps requests across the heterogeneous slices.

``--reshard-to 2x2`` (with ``--sharded``) demonstrates the elastic path:
after the first batch, replica 0 migrates *live* onto a new slice carved
from the pool's leftover devices (``ServeEngine.reshard`` — params move in
memory, no checkpoint), then serves the same requests again; outputs are
verified token-identical across the migration.

``--chaos TRACE.json`` replays a schema-validated failure timeline
(``replica_loss`` / ``straggler`` / ``link_degrade`` / ``link_partition``;
see ``repro.sched_integration.fleet.validate_failure_timeline``) against a
simulator twin of the fleet, reports goodput (requests served inside the
SLO) as a percentage of the failure-free run, and demonstrates live
failover: the first lost replica is removed from the front end and the same
requests re-serve token-identically on the survivors.  Goodput below
``--min-goodput`` (or a failover mismatch) exits non-zero.  Replica targets
in the trace may be unique name *prefixes* of fleet replicas.

``--trace OUT.json`` turns on the full observability stack — a
``repro.obs`` Tracer + MetricsRegistry attached to the front end and every
engine, with the HEFT_RT mapping routed through an instrumented
``MappingFabric`` (decision spans, per-decision latency histogram,
device-resident scheduler counters) — and exports a Perfetto-loadable
Chrome trace with the metrics snapshot embedded.  Output verbosity is the
``REPRO_LOG`` env knob (debug/info/warning/error/silent).
"""

from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.config import ModelConfig
from repro.models.layers import dtype_of
from repro.models.model import init_params_on, logits_fn
from repro.obs import MetricsRegistry, Tracer, get_logger
from repro.obs.metrics import time_s
from repro.serve import HeftFrontEnd, ReplicaHandle, ServeEngine, mesh_backed_fleet

log = get_logger("serve")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--full", action="store_true",
                    help="serve the architecture's published config "
                         "(get_config) instead of its smoke preset")
    ap.add_argument("--num-layers", type=int, default=None,
                    help="with --full: cut depth to this many layers (a "
                         "whole number of layer periods); widths are kept")
    ap.add_argument("--max-len", type=int, default=128,
                    help="per-request cache length (prompt + new tokens)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--prompt-lens", default=None, metavar="N,M,...",
                    help="prompt lengths, cycled over the requests "
                         "(default: uniform in [8, 48)); each distinct "
                         "length compiles its own prefill")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--paged", action="store_true",
                    help="continuous batching: serve through the block-paged "
                         "KV pool (ServeEngine.admit/decode_tick/retire; "
                         "see docs/serving.md), checking request 0 "
                         "against the full-forward reference")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="with --paged: concurrent batch slots per replica")
    ap.add_argument("--page-size", type=int, default=16,
                    help="with --paged: KV page size in tokens (must divide "
                         "the engine max_len)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="with --paged: pool pages per replica (default: "
                         "full occupancy; lower exercises admission "
                         "queueing)")
    ap.add_argument("--fused-scheduler", action="store_true",
                    help="with --paged: run the HEFT_RT admission decision "
                         "inside the decode tick's compiled program "
                         "(MappingFabric backend='fused'; zero host "
                         "scheduling round-trips at steady state — "
                         "docs/scheduling.md)")
    ap.add_argument("--sharded", action="store_true",
                    help="back replicas with mesh slices of the device pool")
    ap.add_argument("--mesh-shapes", default="1x1",
                    help="comma-separated slice shapes for --sharded, "
                         "e.g. 1x1,2x1,2x2")
    ap.add_argument("--reshard-to", default=None, metavar="AxB",
                    help="with --sharded: after serving, migrate replica 0 "
                         "live onto a slice of this shape carved from the "
                         "leftover devices, and re-verify outputs")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="export a Chrome trace (Perfetto) of the run, with "
                         "the metrics snapshot and drained device counters "
                         "embedded")
    ap.add_argument("--chaos", default=None, metavar="TRACE.json",
                    help="replay a schema-validated failure timeline against "
                         "a simulator twin of the fleet and demo live "
                         "failover; exits non-zero below --min-goodput")
    ap.add_argument("--min-goodput", type=float, default=90.0,
                    help="minimum chaos goodput as percent of the "
                         "failure-free run (default 90)")
    ap.add_argument("--slo-s", type=float, default=2.0,
                    help="per-request latency SLO for the goodput metric")
    return ap


def served_config(args) -> ModelConfig:
    """The smoke preset, or with ``--full`` the published config cut only
    in depth (``--num-layers``); every width stays as published."""
    if not args.full:
        if args.num_layers is not None:
            raise SystemExit("--num-layers requires --full")
        return get_smoke_config(args.arch)
    cfg = get_config(args.arch)
    if args.num_layers is not None:
        n, first, period = args.num_layers, cfg.first_dense_layers, cfg.period
        if not (first < n <= cfg.num_layers and (n - first) % period == 0):
            raise SystemExit(
                f"--num-layers must be {first} plus a positive multiple of "
                f"{cfg.name}'s layer period {period}, at most "
                f"{cfg.num_layers}")
        cfg = cfg.with_(num_layers=n)
    return cfg


def make_requests(args, vocab_size: int) -> list[tuple[np.ndarray, int]]:
    rng = np.random.default_rng(0)
    lens = ([int(n) for n in args.prompt_lens.split(",")]
            if args.prompt_lens else None)
    return [
        (rng.integers(0, vocab_size,
                      lens[i % len(lens)] if lens else rng.integers(8, 48)
                      ).astype(np.int32), args.new_tokens)
        for i in range(args.requests)
    ]


# The oracle check's tolerance, in units in the last place (ulps) of the
# config's compute dtype at the magnitude of the top reference logit.  The
# paged tick (a batch of lanes at their own positions, attention over a
# gathered page view) and the reference (one causal forward pass over the
# whole sequence) are different programs: their reductions run in another
# order and each rounds its logits to the compute dtype, so a near-tie can
# flip between them.  On a TPU v5e at bf16 and deepseek-7b widths the
# largest gap measured was 2 ulps (PERF.md).  A wrong computation misses by a top-2 logit gap,
# several ulps at random weights, and does so at many of its steps; a path
# that computes in a lower precision than the config states misses by that
# precision's error, which is many ulps of the stated dtype.
ORACLE_TOL_ULPS = 4


@functools.partial(jax.jit, static_argnums=3)
def _reference_top_and_picked(params, tokens, targets, cfg):
    """Per position of the full forward pass over ``tokens`` (1, S): the top
    logit and the logit of ``targets`` (S,), in float32.  Only these two
    rows leave the device, never the (S, vocab) logits."""
    logits = logits_fn(params, tokens, cfg, remat=False)[0][0]
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return logits.max(axis=1), picked


def reference_gaps(cfg: ModelConfig, engine, prompt: np.ndarray,
                   served: np.ndarray) -> np.ndarray:
    """Score a served sequence against the model's full forward pass.

    For each generated token: how far its logit lies below the top logit of
    the reference row that predicts it, in ulps of ``cfg``'s compute dtype
    at that top logit (0 where the served token is the reference argmax).
    The reference runs ``cfg`` — the config as stated, not the engine's —
    over the served prefix (teacher-forced) on ``engine``'s weights and
    placement.
    """
    with engine._ctx():
        top, picked = _reference_top_and_picked(
            engine.params, jnp.asarray(served[None, :-1]),
            jnp.asarray(served[1:]), cfg)
    top, picked = (np.asarray(x)[len(prompt) - 1:] for x in (top, picked))
    eps = float(jnp.finfo(dtype_of(cfg.compute_dtype)).eps)
    ulp = eps * np.exp2(np.floor(np.log2(np.maximum(np.abs(top), 1e-30))))
    return (top - picked) / ulp


def check_against_reference(cfg: ModelConfig, engine, prompt: np.ndarray,
                            served: np.ndarray) -> tuple[float, str | None]:
    """(largest gap in ulps, None) when every served token is within
    ``ORACLE_TOL_ULPS`` of the reference argmax; otherwise the gap and a
    description of the first step that is not."""
    gaps = reference_gaps(cfg, engine, prompt, served)
    worst = float(gaps.max())
    bad = np.flatnonzero(gaps > ORACLE_TOL_ULPS)
    if not len(bad):
        return worst, None
    k = int(bad[0])
    return worst, (f"step {k} (position {len(prompt) + k}): served token "
                   f"{int(served[len(prompt) + k])} is {gaps[k]:.1f} ulps "
                   f"below the reference's top logit (tolerance "
                   f"{ORACLE_TOL_ULPS})")


def main(argv=None) -> dict:
    """Run the demo; returns what it served, for callers that check it
    (``chip_smoke.py``)."""
    args = build_parser().parse_args(argv)

    cfg = served_config(args)
    key = jax.random.key(0)
    log.info(f"arch={cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
             f"params={cfg.param_count()/1e6:.2f}M {cfg.param_dtype} "
             f"max_len={args.max_len} devices={jax.device_count()}")

    tracer, metrics = (Tracer(), MetricsRegistry()) if args.trace else (None, None)

    spare = []
    if args.sharded:
        shapes = [tuple(int(d) for d in s.split("x"))
                  for s in args.mesh_shapes.split(",")]
        # Each slice initialises its own weights from the key, born under
        # its own shardings: nothing is built whole on one device first.
        fleet, spare = mesh_backed_fleet(cfg, key, shapes,
                                         max_len=args.max_len,
                                         return_spare=True)
        log.info(f"mesh-backed fleet: {[r.mesh_shape for r in fleet]} slices "
                 f"({len(spare)} spare devices)")
    else:
        # One weight tree shared by every replica of the fleet.
        params = init_params_on(key, cfg)
        speeds = [1.0, 0.7, 1.4][: args.replicas] or [1.0]
        fleet = [ReplicaHandle(f"replica{i}(x{s})",
                               ServeEngine(cfg, params, max_len=args.max_len),
                               speed=s)
                 for i, s in enumerate(speeds)]

    fabric = None
    if args.fused_scheduler and not args.paged:
        raise SystemExit("--fused-scheduler requires --paged")
    if args.trace or args.fused_scheduler:
        # Route mapping events through a fabric: with --trace, decision
        # spans + the per-decision latency histogram + device-resident
        # counters (the numpy backend's decisions are bit-identical to the
        # heft_rt_numpy path this launcher uses untraced); with
        # --fused-scheduler, the fused backend whose registers the paged
        # decode tick consumes in-program (docs/scheduling.md).
        from repro.sched_integration.fabric import MappingFabric

        backend = "fused" if args.fused_scheduler else "numpy"
        fabric = MappingFabric(len(fleet), backend=backend, tracer=tracer,
                               metrics=metrics, device_counters=True)
        if args.fused_scheduler:
            log.info(f"fused scheduler: fabric backend={backend} "
                     f"(effective {fabric.backend_effective})")
        if args.trace:
            for r in fleet:
                r.engine.tracer = tracer
    front = HeftFrontEnd(fleet, fabric=fabric, tracer=tracer, metrics=metrics)

    requests = make_requests(args, cfg.vocab_size)
    result = {"cfg": cfg, "front": front, "fabric": fabric,
              "requests": requests}
    if args.paged:
        # Continuous batching: requests join/leave the running batch at the
        # admission tick instead of queueing behind whole generations.
        # Stagger arrivals so later requests land while decode ticks are in
        # flight — the steady-state case the fused scheduler exists for
        # (tick-0 arrivals are cold-start and take the host path).
        arrivals = [min(i, 2 * args.new_tokens // 3)
                    for i in range(len(requests))]
        (seqs, stats), dt = time_s(
            front.run_continuous, requests, arrival_ticks=arrivals,
            max_batch=args.max_batch,
            page_size=args.page_size, num_pages=args.num_pages)
        outs = [s[None, :] for s in seqs]      # run_batch-shaped, for demos
        counts = stats["processed"]
        log.info(f"{len(outs)} requests in {dt:.2f}s paged "
                 f"({sum(len(p)+args.new_tokens for p,_ in requests)/dt:.0f} "
                 f"tok/s, {stats['ticks']} ticks, "
                 f"{stats['allocated']} pages allocated == "
                 f"{stats['freed']} freed)")
        if args.fused_scheduler:
            log.info(f"scheduling decisions: {stats['fused_decisions']} "
                     f"fused in-tick, {stats['host_decisions']} host "
                     f"(cold-start/idle)")
        result["stats"] = stats
        worst, diverged = check_against_reference(
            cfg, front.replicas[0].engine, requests[0][0], seqs[0])
        if diverged is not None:
            raise SystemExit("paged output disagrees with the full-forward "
                             "reference: " + diverged)
        log.info(f"request 0 verified against the full-forward reference "
                 f"(largest gap {worst:.1f} of {ORACLE_TOL_ULPS} ulps)")
    else:
        (outs, counts), dt = time_s(front.run_batch, requests)
        log.info(f"{len(outs)} requests in {dt:.2f}s "
                 f"({sum(len(p)+args.new_tokens for p,_ in requests)/dt:.0f} tok/s)")
    result["outputs"] = outs
    log.info(f"request distribution (HEFT_RT): {counts}")
    log.info(f"sample output ids: {outs[0][0, -8:].tolist()}")

    if args.reshard_to:
        if not args.sharded:
            raise SystemExit("--reshard-to requires --sharded")
        from repro.launch.mesh import make_debug_mesh

        shape = tuple(int(d) for d in args.reshard_to.split("x"))
        need = int(np.prod(shape))
        if len(spare) < need:
            raise SystemExit(
                f"--reshard-to {args.reshard_to} needs {need} spare devices, "
                f"pool has {len(spare)} left after the fleet slices")
        target = make_debug_mesh(shape, devices=spare[:need])
        old = fleet[0].mesh_shape
        fleet[0].engine.reshard(target)
        fleet[0].sync_mesh_identity()     # speed/rates follow the new slice
        log.info(f"replica 0 resharded live: {old} -> "
                 f"{fleet[0].mesh_shape} (speed x{fleet[0].speed:.0f})")
        outs2, _ = front.run_batch(requests)
        same = all(np.array_equal(a, b) for a, b in zip(outs, outs2))
        log.info(f"post-reshard outputs "
                 f"{'token-identical' if same else 'MISMATCH'}")
        if not same:
            raise SystemExit(1)     # the verification must fail loudly

    if args.chaos:
        _run_chaos(args, front, requests, outs, tracer, metrics)

    if args.trace:
        # Drained device counters land in the metrics snapshot next to the
        # latency histograms, so one artifact carries the whole picture.
        for name, value in fabric.drain_counters().items():
            metrics.gauge("fabric.device", counter=name).set(value)
        tracer.export(args.trace, metrics=metrics)
        log.info(f"trace: {args.trace} ({len(tracer)} events, "
                 f"{len(metrics)} metrics)")
    return result


def _resolve_targets(timeline, names):
    """Resolve replica-kind targets against the fleet, accepting unique name
    prefixes (so a generic trace says ``replica1`` and matches
    ``replica1(x0.7)``).  Link targets pass through untouched."""
    from repro.sched_integration import FailureEvent

    out = []
    for e in timeline:
        if e.kind in ("replica_loss", "straggler"):
            hits = [n for n in names
                    if n == e.target or n.startswith(e.target)]
            if len(hits) != 1:
                raise SystemExit(
                    f"chaos target {e.target!r} matches "
                    f"{hits or 'no replicas'} in {names}")
            if hits[0] != e.target:
                e = FailureEvent(e.t, e.kind, hits[0], e.duration_s,
                                 e.factor, e.reason)
        out.append(e)
    return out


def _run_chaos(args, front, requests, outs, tracer, metrics) -> None:
    """The --chaos path: simulator-twin goodput gate + live failover demo."""
    from repro.sched_integration import (
        POLICIES, Replica, goodput, load_failure_timeline, make_requests,
        simulate_serving, spine_topology)

    timeline = load_failure_timeline(args.chaos)
    names = [r.name for r in front.replicas]
    timeline = _resolve_targets(timeline, names)

    # Simulator twin: aggregate rates follow each handle's speed, scaled to
    # pod-class capacity (a speed-1.0 replica ≈ a 256-chip v5e slice at 50%
    # MFU), so the timeline replays against the live fleet's relative
    # capacities at serving-realistic service times.  The offered load sits
    # at ~60% of fleet capacity — the N+1 headroom a production fleet
    # carries — so the goodput gate measures *recovery*, not the bare
    # arithmetic of lost capacity.
    twin = [Replica(r.name, 25000.0 * r.speed, 126000.0 * r.speed)
            for r in front.replicas]
    rate = 24.0 * sum(r.speed for r in front.replicas)
    topo = None
    if any(e.kind in ("link_degrade", "link_partition") for e in timeline):
        # One pod per replica behind a shared spine — the maximally
        # contended fabric; link targets address "podI:spine".
        pod_of = {r.name: f"pod{i}" for i, r in enumerate(twin)}
        topo = spine_topology(["gw"] + sorted(set(pod_of.values())), 100.0,
                              pod_of=pod_of, gateway="gw")
    load = make_requests(rate, 2.0, seed=0)
    clean = simulate_serving(twin, load, POLICIES["heft_rt"](),
                             active_params=7e9)
    chaos = simulate_serving(twin, load, POLICIES["heft_rt"](),
                             active_params=7e9, failure_events=timeline,
                             topology=topo, tracer=tracer, metrics=metrics)
    g_clean = goodput(clean, load, args.slo_s)
    g_chaos = goodput(chaos, load, args.slo_s)
    pct = 100.0 * g_chaos / max(g_clean, 1)
    requeued = int(chaos.requeued.sum())
    unserved = int((~chaos.served_mask).sum())
    log.info(f"chaos: {len(timeline)} failures, goodput {g_chaos}/{g_clean} "
             f"({pct:.1f}% of failure-free), {requeued} re-queued, "
             f"{unserved} unserved")

    # Live failover: kill the first lost replica on the real front end and
    # re-serve the same requests — token-identical on the survivors proves
    # no request depends on the dead engine.
    losses = [e for e in timeline if e.kind == "replica_loss"]
    if losses and len(front.replicas) > 1:
        gone = front.remove_replica(losses[0].target)
        outs2, _ = front.run_batch(requests)
        same = all(np.array_equal(a, b) for a, b in zip(outs, outs2))
        log.info(f"failover: lost {gone.name}, re-served "
                 f"{len(outs2)} requests on {len(front.replicas)} survivors "
                 f"({'token-identical' if same else 'MISMATCH'})")
        if not same:
            raise SystemExit(1)

    if pct < args.min_goodput:
        raise SystemExit(
            f"chaos goodput {pct:.1f}% below --min-goodput "
            f"{args.min_goodput}%")


if __name__ == "__main__":
    enable_compile_cache()
    main()
