"""Training loop: jit'd train step (grad-accum, optional cross-pod int8
gradient compression), checkpoint/restart orchestration.

``make_train_step`` builds the pjit-able step used both by the CPU examples
and the 512-device dry-run; ``Trainer`` adds the fault-tolerance loop around
it (periodic async checkpoints, exact restart from the latest checkpoint, a
deterministic step-indexed data stream so restarts replay nothing).

Every step path threads the int8 error-feedback residual as first-class
state — ``step(params, opt_state, residual, batch) → (params, opt_state,
residual, metrics)`` — with ``residual=None`` the valid steady state on
uncompressed paths.  On the compressed pod path the residual is the stacked
per-pod tree from ``dist.compression`` (leaf ``(num_pods, *grad.shape)``,
sharded ``P(pod)``), carried across steps and checkpointed next to
params/opt so restarts stay bit-exact; dropping it would re-bias the int8
collective every step after a crash (the exact failure mode error feedback
exists to prevent).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.dist.compression import (
    EXACT_BYTES_PER_ELEM,
    WIRE_BYTES_PER_ELEM,
    WIRE_SCALE_BYTES_PER_LEAF,
    compressed_psum_mean,
    init_residual,
    reshard_residual,
)
from repro.dist.hints import sharding_policy
from repro.dist.sharding import MeshAxes, activation_hint_policy, reshard_tree
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig, ShapeConfig
from repro.models.model import init_params, loss_fn
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state


# Metrics that are COUNTS over the batch (extensive): reducers across
# microbatches and pods SUM these so totals stay comparable to a plain
# single-device step; everything else (ce, aux/z losses, ...) is a
# per-token mean (intensive) and is averaged.
EXTENSIVE_METRICS = frozenset({"expert_load"})


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig, *,
                    microbatches: int = 1,
                    pod_axis: str | None = None,
                    compress_pods: bool = False,
                    mesh=None):
    """Returns train_step(params, opt_state, residual, batch)
    → (params, opt_state, residual, metrics).

    * ``microbatches > 1``: gradient accumulation via lax.scan over batch
      slices (sum of per-micro grads, normalized once; loss AND per-micro
      metrics — ce, MoE aux — are accumulated and meaned the same way).
    * ``pod_axis`` + ``compress_pods``: gradients are computed per-pod via
      vmap over a leading pod dim (intra-pod layout stays GSPMD-auto, and
      backward emits no implicit cross-pod reduce) and mean-reduced
      cross-pod with the int8+error-feedback collective inside a reduce-only
      shard_map manual region over the pod axis.

    ``residual`` is the error-feedback state.  Uncompressed paths pass it
    through untouched (``None`` is the steady state).  The compressed pod
    path consumes/produces the stacked per-pod tree (leaf ``(num_pods,
    *grad.shape)`` f32, sharded ``P(pod_axis)`` — each pod owns its own
    slice; it is per-pod local error and is never reduced).  ``None`` is
    accepted as a cold start there too and is promoted to zeros.
    """

    def grads_of(params, tokens, labels):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, tokens, labels, cfg)
            return loss, metrics, grads

        B = tokens.shape[0]
        assert B % microbatches == 0
        mb = B // microbatches
        tk = tokens.reshape(microbatches, mb, -1)
        lb = labels.reshape(microbatches, mb, -1)

        def micro(carry, xs):
            g_acc, l_acc, m_acc = carry
            t, l = xs
            (loss, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, t, l, cfg)
            return (jax.tree.map(jnp.add, g_acc, g), l_acc + loss,
                    jax.tree.map(jnp.add, m_acc, m)), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        # metrics structure (ce, MoE aux, ...) comes from an abstract trace —
        # the accumulator must exist before the scan body runs.
        _, m_shape = jax.eval_shape(
            lambda p, t, l: loss_fn(p, t, l, cfg), params, tk[0], lb[0])
        m0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), m_shape)
        (g, ltot, mtot), _ = jax.lax.scan(
            micro, (g0, jnp.zeros(()), m0), (tk, lb))
        g = jax.tree.map(lambda x: x / microbatches, g)
        # intensive metrics mean across microbatches; extensive counts sum
        # (same global batch → same total whatever the accumulation factor,
        # which expert-placement consumers rely on)
        metrics = {k: (v if k in EXTENSIVE_METRICS else v / microbatches)
                   for k, v in mtot.items()}
        return ltot / microbatches, metrics, g

    def plain_step(params, opt_state, residual, batch):
        loss, metrics, grads = grads_of(params, batch["tokens"], batch["labels"])
        params, opt_state, om = adamw_update(grads, opt_state, params, opt_cfg)
        return params, opt_state, residual, {"loss": loss, **metrics, **om}

    if pod_axis is None:
        return plain_step

    # Everything but the pod axis stays GSPMD-auto.  The gradient compute is
    # vmapped over a leading pod dim (NOT run inside the manual region: the
    # model is scan-over-layers, and lax.scan inside a partially-auto
    # shard_map body breaks the SPMD partitioner — an all-in-one manual
    # pod_step does not compile on a multi-axis mesh).  Only the cross-pod
    # *reduction* is manual over ``pod_axis``; that body is scan-free, and
    # it is the one place wire format matters.
    num_pods = mesh.shape[pod_axis]
    data_axis = "data" if "data" in mesh.axis_names else None

    def _pod_split(x):
        """(B, ...) → (num_pods, B/num_pods, ...), pod/data-sharded."""
        assert x.shape[0] % num_pods == 0, (x.shape, num_pods)
        x = x.reshape((num_pods, x.shape[0] // num_pods) + x.shape[1:])
        spec = P(pod_axis, data_axis, *([None] * (x.ndim - 2)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))

    def _stack_spec(e):
        # full-rank P(pod, None, ...): dim 0 is the owning pod
        return P(pod_axis, *([None] * (e.ndim - 1)))

    def exact_pod_step(params, opt_state, residual, batch):
        tokens = _pod_split(batch["tokens"])
        labels = _pod_split(batch["labels"])
        loss, metrics, grads = jax.vmap(grads_of, in_axes=(None, 0, 0))(
            params, tokens, labels)
        grads = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
        loss = jnp.mean(loss)
        metrics = _pod_metrics(metrics)
        params, opt_state, om = adamw_update(grads, opt_state, params,
                                             opt_cfg)
        return params, opt_state, residual, {"loss": loss, **metrics, **om}

    def _pod_metrics(metrics):
        # mean intensive metrics over pods; extensive counts are per-pod
        # partials whose global value is the SUM over pod slices (matches
        # the single-device count for the same global batch)
        return {k: (jnp.sum(v, axis=0) if k in EXTENSIVE_METRICS
                    else jnp.mean(v, axis=0)) for k, v in metrics.items()}

    def compressed_pod_step(params, opt_state, residual, batch):
        if residual is None:          # cold start: zero error feedback
            residual = init_residual(params, num_pods)
        tokens = _pod_split(batch["tokens"])
        labels = _pod_split(batch["labels"])
        # params broadcast over the vmapped pod dim: each pod's grads depend
        # only on its batch slice, so backward emits NO implicit cross-pod
        # reduce — the explicit int8 collective below is the only traffic
        # over the slow links.
        loss, metrics, grads = jax.vmap(grads_of, in_axes=(None, 0, 0))(
            params, tokens, labels)

        def reduce_body(grads, residual):
            # local slices are (1, *shape): squeeze the pod dim for the
            # collective, restack the new per-pod error on the way out.
            g = jax.tree.map(lambda x: x[0], grads)
            e = jax.tree.map(lambda x: x[0], residual)
            mean, new_err = compressed_psum_mean(g, pod_axis, e)
            return mean, jax.tree.map(lambda x: x[None], new_err)

        gspec = jax.tree.map(_stack_spec, grads)
        rspec = jax.tree.map(_stack_spec, residual)
        reduce_fn = jax.shard_map(
            reduce_body, mesh=mesh, in_specs=(gspec, rspec),
            # the mean leaves replicated; the residual leaves P(pod)-sharded
            # (per-pod local error — never reduced)
            out_specs=(jax.tree.map(lambda _: P(), grads), rspec),
            axis_names={pod_axis}, check_vma=False)
        grads, residual = reduce_fn(grads, residual)
        loss = jnp.mean(loss)
        metrics = _pod_metrics(metrics)
        params, opt_state, om = adamw_update(grads, opt_state, params,
                                             opt_cfg)
        return params, opt_state, residual, {"loss": loss, **metrics, **om}

    return compressed_pod_step if compress_pods else exact_pod_step


@dataclass
class TrainerConfig:
    total_steps: int = 100
    checkpoint_every: int = 25
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3
    log_every: int = 10
    seed: int = 0
    # --- distribution / accumulation knobs -------------------------------
    # microbatches: gradient accumulation factor (1 = none).
    # mesh_shape: build a mesh over ("pod", "data", "model")[:len(shape)];
    #   None keeps the single-device fast path.  The leading axis is the
    #   pod axis (data parallelism over slow links).
    # compress_pods: int8 error-feedback cross-pod gradient reduction (the
    #   residual becomes checkpointed train-step state).
    microbatches: int = 1
    mesh_shape: tuple[int, ...] | None = None
    pod_axis: str = "pod"
    compress_pods: bool = False


class Trainer:
    """Single-process training driver with checkpoint/restart fault tolerance.

    With ``mesh_shape`` set the Trainer is mesh-aware: it constructs the
    multi-pod mesh and the activation sharding policy itself, runs the pod
    train step (optionally int8-compressed over the pod axis), and
    checkpoints the error-feedback residual next to params/opt.  Restarts
    are bit-exact at the same pod count; a restore onto a different pod
    count reshards the residual via ``dist.compression.reshard_residual``
    (mean-broadcast — preserves the applied correction Σe/n) and replaces
    every leaf on the new mesh.
    """

    def __init__(self, cfg: ModelConfig, opt_cfg: AdamWConfig,
                 data_cfg: DataConfig, tcfg: TrainerConfig, *,
                 tracer=None, metrics=None):
        self.cfg, self.opt_cfg, self.tcfg = cfg, opt_cfg, tcfg
        self.tracer = tracer            # repro.obs.Tracer: per-step spans
        self.metrics = metrics          # repro.obs.MetricsRegistry
        self.pipeline = TokenPipeline(data_cfg)
        self.ckpt = Checkpointer(tcfg.checkpoint_dir, keep=tcfg.keep_checkpoints)

        self.mesh = None
        self.policy = None
        pod_axis = None
        if tcfg.compress_pods and tcfg.mesh_shape is None:
            raise ValueError(
                "TrainerConfig(compress_pods=True) requires mesh_shape — "
                "without a pod axis the int8 collective would be silently "
                "skipped (use mesh_shape=(1,) for a single-pod mesh)")
        if tcfg.mesh_shape is not None:
            names = (tcfg.pod_axis, "data", "model")[:len(tcfg.mesh_shape)]
            self.mesh = make_mesh(tuple(tcfg.mesh_shape), names)
            pod_axis = tcfg.pod_axis
            if "model" in names:
                # hint policy for the GSPMD-auto region *inside* the manual-
                # over-pod step: batch-like dims over data, TP over model
                # (pod is the manual axis, so hints never mention it).
                shape_cfg = ShapeConfig("train", "train", data_cfg.seq_len,
                                        data_cfg.global_batch)
                self.policy = activation_hint_policy(
                    cfg, MeshAxes(pod=None), shape_cfg)
        self.pod_axis = pod_axis
        self.num_pods = self.mesh.shape[pod_axis] if pod_axis else 1
        self.compressed = bool(pod_axis and tcfg.compress_pods)

        step = make_train_step(cfg, opt_cfg,
                               microbatches=tcfg.microbatches,
                               pod_axis=pod_axis,
                               compress_pods=tcfg.compress_pods,
                               mesh=self.mesh)
        self.step_fn = jax.jit(step, donate_argnums=(0, 1, 2))
        # final residual of the last COMPLETED run() (None before): mid-run
        # values are donated back into step_fn and must not be exposed
        self.last_residual = None

    # ---- state ------------------------------------------------------------

    def _zero_residual(self, params):
        return (init_residual(params, self.num_pods) if self.compressed
                else None)

    def _residual_shardings(self, residual):
        return jax.tree.map(
            lambda _: NamedSharding(self.mesh, P(self.pod_axis)), residual)

    def init_or_restore(self):
        params = init_params(jax.random.key(self.tcfg.seed), self.cfg)
        opt_state = init_opt_state(params, self.opt_cfg)
        residual = self._zero_residual(params)
        latest = self.ckpt.latest_step()
        if latest is None:
            return params, opt_state, residual, 0
        if residual is None:
            state = self.ckpt.restore({"params": params, "opt": opt_state})
            return state["params"], state["opt"], None, latest

        # compressed path: ONE checkpoint read covers params+opt+residual
        try:
            saved_pods = int(self.ckpt.read_metadata().get("num_pods",
                                                           self.num_pods))
        except FileNotFoundError:
            saved_pods = self.num_pods
        template = {"params": params, "opt": opt_state, "residual": residual}
        try:
            if saved_pods == self.num_pods:
                # same pod count: residual leaves restore bit-exact, placed
                # P(pod) on this trainer's mesh (params/opt replicate)
                sh = {"params": jax.tree.map(
                          lambda _: NamedSharding(self.mesh, P()), params),
                      "opt": jax.tree.map(
                          lambda _: NamedSharding(self.mesh, P()), opt_state),
                      "residual": self._residual_shardings(residual)}
                state = self.ckpt.restore(template, shardings=sh)
                return (state["params"], state["opt"], state["residual"],
                        latest)
            state = self.ckpt.restore(template)
        except KeyError:
            # pre-residual checkpoint: cold-start the error feedback
            state = self.ckpt.restore({"params": params, "opt": opt_state})
            return state["params"], state["opt"], residual, latest
        # elastic pod-count change: rebuild the stack (Σe/n preserved) and
        # place each leaf on the new mesh
        res = reshard_residual(state["residual"], self.num_pods)
        res = reshard_tree(res, self._residual_shardings(res))
        return state["params"], state["opt"], res, latest

    def save(self, step: int, params, opt_state, residual) -> None:
        # residual=None flattens to nothing — uncompressed checkpoints keep
        # the pre-residual layout.
        self.ckpt.save(step, {"params": params, "opt": opt_state,
                              "residual": residual},
                       metadata={"num_pods": self.num_pods})

    # ---- loop --------------------------------------------------------------

    def run(self, steps: int | None = None, inject_failure_at: int | None = None):
        """Run to total_steps (resuming if checkpoints exist).

        ``inject_failure_at``: raise after that many NEW steps — used by the
        fault-tolerance tests/examples to prove bitwise-exact restart.
        """
        params, opt_state, residual, start = self.init_or_restore()
        total = steps if steps is not None else self.tcfg.total_steps
        history = []
        done = 0
        obs_on = self.tracer is not None or self.metrics is not None
        step_hist = (self.metrics.histogram("train.step_s")
                     if self.metrics is not None else None)
        # Cross-pod wire bytes per step, from the dist.compression payload
        # model: each pod ships every grad leaf over the slow links once —
        # int8 payload + f32 scales when compressed, f32 when exact.  Zero
        # with no pod axis (no slow links to account).
        wire_step = 0
        if obs_on and self.pod_axis is not None and self.num_pods > 1:
            leaves = jax.tree.leaves(params)
            n_elems = sum(x.size for x in leaves)
            wire_step = (
                WIRE_BYTES_PER_ELEM * n_elems
                + WIRE_SCALE_BYTES_PER_LEAF * len(leaves)
                if self.compressed else EXACT_BYTES_PER_ELEM * n_elems)
        with contextlib.ExitStack() as stack:
            if self.mesh is not None:
                stack.enter_context(jax.set_mesh(self.mesh))
                if self.policy is not None:
                    stack.enter_context(sharding_policy(self.policy))
            for step in range(start, total):
                batch = self.pipeline.batch_at(step)
                t0 = time.perf_counter() if obs_on else 0.0
                params, opt_state, residual, metrics = self.step_fn(
                    params, opt_state, residual,
                    {k: jnp.asarray(v) for k, v in batch.items()})
                if obs_on:
                    dt = time.perf_counter() - t0
                    if step_hist is not None:
                        step_hist.record(dt)
                    if self.metrics is not None and wire_step:
                        self.metrics.counter("train.wire_bytes").inc(wire_step)
                    if self.tracer is not None:
                        self.tracer.complete("train.step", t0, dt, step=step)
                if (step + 1) % self.tcfg.checkpoint_every == 0 or step + 1 == total:
                    self.save(step + 1, params, opt_state, residual)
                if (step + 1) % self.tcfg.log_every == 0 or step + 1 == total:
                    history.append((step + 1, float(metrics["loss"])))
                done += 1
                if inject_failure_at is not None and done >= inject_failure_at:
                    self.ckpt.wait()
                    raise RuntimeError(f"injected failure at step {step + 1}")
        self.ckpt.wait()
        self.last_residual = residual
        return params, opt_state, history
