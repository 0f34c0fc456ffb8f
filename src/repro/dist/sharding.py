"""Mesh sharding rules: PartitionSpec trees for params, optimizer moments,
caches, batches, and the default activation hint policy.

Everything here is pure spec construction — no devices are touched, so these
functions run identically on a laptop, in the 512-fake-device dry-run, and on
real pods.  Specs are *named* (logical ``pod`` / ``data`` / ``model`` axes via
:class:`MeshAxes`); ``named(mesh, tree)`` binds them to a concrete mesh.

Parameter layout (the baseline the §Perf hillclimb variants mutate):

* 2-D projections are Megatron-style: column-parallel inputs ``(D, F)`` shard
  ``P(data, model)`` (FSDP on d_model, TP on the output features), row-
  parallel outputs ``(F, D)`` shard ``P(model, data)``.
* MoE expert stacks ``(E, D, F)`` / ``(E, F, D)`` shard experts over
  ``model`` and d_model over ``data`` (ZeRO-3 on the expert weights — they
  dominate parameter bytes for every assigned MoE arch).
* ``embed (V, D)`` → ``P(model, data)``; ``lm_head (D, V)`` → ``P(data,
  model)``; 1-D leaves (norms, biases, Mamba ``D``/``dt_bias``) replicate.
* Leaves stacked under ``stages`` (the scan-over-layers stack) get a leading
  ``None`` for the stage dim.

``fsdp=False`` drops the ``data`` axis from weights (TP-only replication);
``fsdp_experts_only=True`` re-enables it for expert tensors alone (attention
and dense weights are small enough replicated — their per-layer FSDP gathers
disappear, experts keep ZeRO-3, which they need to fit).
"""

from __future__ import annotations

import dataclasses

import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import tree_map_with_path

from repro.models import model as model_mod
from repro.models.config import ModelConfig, ShapeConfig


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Logical mesh axis names.  ``pod=None`` on single-pod meshes."""

    pod: str | None = None
    data: str = "data"
    model: str = "model"

    @property
    def batch(self):
        """Axis (or axes) batch-like leading dims shard over."""
        return (self.pod, self.data) if self.pod else self.data

    @property
    def batch_tuple(self) -> tuple[str, ...]:
        return (self.pod, self.data) if self.pod else (self.data,)


def named(mesh, tree):
    """Bind a PartitionSpec tree to ``mesh`` as NamedShardings."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def reshard_tree(tree, new_shardings, *, old_shardings=None):
    """Migrate a pytree between shardings purely in memory.

    The one resharding primitive every elastic path shares: the checkpoint
    restore (``Checkpointer.restore(shardings=...)``), the trainer's
    pod-count residual migration, and a live ``ServeEngine.reshard`` all
    re-place leaves with this helper — none of them needs a disk round-trip.

    ``new_shardings`` is a pytree matching ``tree`` (or a prefix of it) whose
    leaves are ``jax.sharding.Sharding``s; ``None`` leaves are left untouched.
    ``old_shardings``, when given, marks leaves whose placement is already
    correct (``old == new``) so their transfer is skipped.

    Leaves move device to device with ``jax.device_put``, also between
    disjoint mesh slices.
    """
    flat_t, tdef = jax.tree.flatten(tree)
    flat_new = tdef.flatten_up_to(new_shardings)
    flat_old = (tdef.flatten_up_to(old_shardings)
                if old_shardings is not None else [None] * len(flat_t))

    def place(x, new, old):
        if new is None or (old is not None and old == new):
            return x
        return jax.device_put(x, new)

    return tdef.unflatten(
        [place(x, n, o) for x, n, o in zip(flat_t, flat_new, flat_old)])


def _path_keys(path) -> list[str]:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return out


# Megatron column-parallel (input dim, output features) / row-parallel
# (input features, output dim) 2-D projections, by leaf name.
_COL2 = {"wq", "wk", "wv", "w_gate", "w_up", "in_proj"}
_ROW2 = {"wo", "w_down", "out_proj"}
# MLA low-rank down-projections: (D, rank) — rank too small to TP-shard.
_MLA_DOWN = {"w_dkv", "w_kr", "w_dq"}
# MLA up-projections: (rank, H, head_dim) — heads over model.
_MLA_UP = {"w_uk", "w_uv", "w_uq"}


def _param_rule(keys: list[str], shape: tuple[int, ...], ax: MeshAxes,
                fsdp: bool, fsdp_experts_only: bool) -> P:
    """Spec for one (possibly stage-stacked) parameter leaf."""
    stacked = "stages" in keys
    name = keys[-1]
    dims = shape[1:] if stacked else shape
    nd = len(dims)
    is_expert = "experts" in keys
    d = ax.data if (fsdp or (fsdp_experts_only and is_expert)) else None
    m = ax.model

    if nd <= 1:
        spec = P()
    elif name == "embed":
        spec = P(m, d)
    elif name == "lm_head":
        spec = P(d, m)
    elif is_expert and nd == 3:
        # (E, D, F) gate/up vs (E, F, D) down: d_model gets the FSDP axis
        spec = P(m, d, None) if name in ("w_gate", "w_up") else P(m, None, d)
    elif name == "router":
        spec = P(d, None)
    elif name in _MLA_DOWN:
        spec = P(d, None)
    elif name in _MLA_UP:
        spec = P(None, m, None)
    elif name == "wq" and nd == 3:         # MLA direct q: (D, H, e)
        spec = P(d, m, None)
    elif name in _COL2:
        spec = P(d, m)
    elif name in _ROW2:
        spec = P(m, d)
    elif name == "x_proj":                 # mamba (dI, R + 2N)
        spec = P(m, None)
    elif name == "dt_proj":                # mamba (R, dI)
        spec = P(None, m)
    elif name == "conv_w":                 # mamba depthwise (K, dI)
        spec = P(None, m)
    elif name == "A_log":                  # mamba (dI, N)
        spec = P(m, None)
    else:
        spec = P()

    if stacked and len(tuple(spec)) > 0:
        spec = P(None, *tuple(spec))
    elif stacked:
        spec = P(None)
    return spec


def param_pspecs(cfg: ModelConfig, ax: MeshAxes, *, fsdp: bool = True,
                 fsdp_experts_only: bool = False):
    """PartitionSpec tree matching ``model.param_specs(cfg)`` leaf-for-leaf."""
    shapes = model_mod.param_specs(cfg)
    return tree_map_with_path(
        lambda path, leaf: _param_rule(_path_keys(path), tuple(leaf.shape),
                                       ax, fsdp, fsdp_experts_only),
        shapes)


def opt_pspecs(param_pspecs, moment_dtype: str, ax: MeshAxes, *,
               param_shapes=None):
    """Optimizer-state specs mirroring ``optim.adamw.init_opt_state``.

    Moments inherit the parameter spec leaf-by-leaf.  For ``int8`` moments,
    ≥2-D leaves are stored as ``{"q": int8 param-shaped, "scale": (..., 1)}``
    (see optim/adamw.py) — ``q`` keeps the param spec, ``scale`` drops the
    last (length-1) dim's axis.  ``param_shapes`` (ShapeDtypeStruct tree, from
    ``model.param_specs``) supplies leaf ranks; without it the spec's own
    length is used, which is only correct for full-rank specs.
    """
    def moment(spec: P, ndim: int):
        if moment_dtype == "int8" and ndim >= 2:
            entries = list(tuple(spec)) + [None] * (ndim - len(tuple(spec)))
            return {"q": spec, "scale": P(*entries[:-1], None)}
        return spec

    if param_shapes is not None:
        m = jax.tree.map(lambda sh, sp: moment(sp, len(sh.shape)),
                         param_shapes, param_pspecs)
    else:
        m = jax.tree.map(lambda sp: moment(sp, len(tuple(sp))), param_pspecs,
                         is_leaf=lambda x: isinstance(x, P))
    return {"step": P(), "m": m, "v": m}


def batch_pspec(ax: MeshAxes, shape_cfg: ShapeConfig | None = None, *,
                batch_shard: bool = True) -> P:
    """(B, S) token/label batches: batch over (pod,)data, sequence local.

    ``batch_shard=False`` replicates the batch dim — the serve-replica
    layout, where per-request batches are tiny and the mesh slice's
    parallelism is all tensor/FSDP.
    """
    return P(ax.batch if batch_shard else None, None)


def _cache_rule(keys: list[str], ax: MeshAxes, seq_shard: bool,
                batch_shard: bool = True) -> P:
    stacked = "stages" in keys
    name = keys[-1]
    b, m = ax.batch if batch_shard else None, ax.model
    if name in ("k", "v"):            # (B, Smax, KV, hd)
        spec = P(b, m, None, None) if seq_shard else P(b, None, m, None)
    elif name in ("ckv", "kr"):       # MLA latent (B, Smax, R/rope)
        spec = P(b, m, None) if seq_shard else P(b, None, None)
    elif name == "conv":              # mamba (B, K-1, dI)
        spec = P(b, None, m)
    elif name == "ssm":               # mamba (B, dI, N)
        spec = P(b, m, None)
    else:
        spec = P(b)
    return P(None, *tuple(spec)) if stacked else spec


def cache_pspecs(cfg: ModelConfig, ax: MeshAxes, shape_cfg: ShapeConfig, *,
                 seq_shard: bool = False, batch_shard: bool = True):
    """Specs for the KV/SSM cache tree of ``model.cache_specs``.

    Default: batch over (pod,)data and KV heads over ``model``.
    ``seq_shard=True`` is the flash-decode layout — cache *sequence* over
    ``model`` (padding-free for every head count; see hillclimb
    ``flashdecode``).  ``batch_shard=False`` replicates the batch dim
    (serve-replica layout).
    """
    specs = model_mod.cache_specs(cfg, shape_cfg.global_batch,
                                  shape_cfg.seq_len)
    return tree_map_with_path(
        lambda path, leaf: _cache_rule(_path_keys(path), ax, seq_shard,
                                       batch_shard),
        specs)


def activation_hint_policy(cfg: ModelConfig, ax: MeshAxes,
                           shape_cfg: ShapeConfig, *,
                           model_axis_size: int | None = None,
                           batch_shard: bool = True) -> dict:
    """Default name → PartitionSpec policy for the model's hint sites.

    Baseline layout: batch-like dims over (pod,)data everywhere; sequence
    over ``model`` at layer boundaries for train/prefill (decode has S=1);
    heads / hidden / d_inner over ``model`` inside the blocks.  MoE dispatch
    groups shard over *all* mesh axes so the (B,S,D) → (G,Tl,D) regroup
    splits at existing shard boundaries, and expert rows put E over ``model``
    and rows over the batch axes (the EP exchange is the two all-to-alls).

    ``model_axis_size`` additionally pins ``__moe_groups__`` =
    global_batch × model-axis-size — the group count for which the regroup
    moves zero bytes (see moe._group_count).  ``batch_shard=False``
    replicates batch-like dims (the serve-replica layout: tensor-parallel
    heads/hidden only, request batches too small to split).
    """
    b, m = ax.batch if batch_shard else None, ax.model
    seq = m if shape_cfg.kind in ("train", "prefill") else None
    pol: dict = {
        "layer_boundary": P(b, seq, None),
        "logits": P(b, None, m),
        "embed_grad": P(m, ax.data),
        "ffn_hidden": P(b, None, m),
    }
    kinds = {cfg.layer_kind(i) for i in range(cfg.num_layers)}
    if "attn" in kinds:
        pol["attn_heads"] = P(b, None, m, None)
    if "mamba" in kinds:
        pol["mamba_inner"] = P(b, None, m)
    if cfg.moe is not None:
        pol["moe_rows"] = P(m, b, None)
        pol["moe_rows4"] = P(m, b, None, None)
        # Group-layout hints activate the manual shard_map dispatch (see
        # moe._maybe_shard_map), which requires the group dim to divide the
        # full (pod, data, model) extent — guaranteed only when the caller
        # pins the model-axis size and tokens are plentiful (train/prefill).
        # Decode (T = B tokens) keeps GSPMD-auto dispatch: the capacity
        # scatter is tiny there and arbitrary group counts stay legal.
        if model_axis_size is not None and shape_cfg.kind in ("train",
                                                              "prefill"):
            gax = ax.batch_tuple + (m,)
            pol["moe_groups"] = P(gax, None, None)
            pol["moe_groups4"] = P(gax, None, None, None)
            pol["moe_logits"] = P(gax, None, None)
            pol["__moe_groups__"] = shape_cfg.global_batch * model_axis_size
    return pol


def page_pspecs(cfg: ModelConfig, ax: MeshAxes, *, seq_shard: bool = False):
    """Specs for a ``serve.paging`` page-pool tree (continuous batching).

    Pool leaves have the same rank as their dense cache counterparts — the
    batch axis becomes the page (or state-slot) axis and ``Smax`` becomes
    ``page_size`` — so the ``_cache_rule`` name-based specs apply
    *structurally*: the page dim replicates exactly like the serve-replica
    batch dim (``batch_shard=False``), ``page_size`` takes whatever the
    sequence dim would (KV heads stay over ``model``; ``seq_shard=True``
    moves the flash-decode split onto the page_size dim).  One rule, two
    layouts — gather/scatter between pool and dense view is then a pure
    page-axis permutation that GSPMD never reshards for.
    """
    shape_cfg = ShapeConfig("serve", "decode", 1, 1)   # structure-only
    return cache_pspecs(cfg, ax, shape_cfg, seq_shard=seq_shard,
                        batch_shard=False)


def replica_pspecs(cfg: ModelConfig, ax: MeshAxes, *, fsdp: bool = True,
                   seq_shard: bool = False) -> dict:
    """Spec bundle for one mesh-backed serve replica (see serve/engine.py).

    A replica's mesh slice parallelizes the *model* (TP heads/hidden, FSDP
    weights), never the request batch — per-request batches are tiny, so
    batch-like dims replicate and any slice shape serves any batch size.
    Returns ``{"params", "cache", "batch", "policy"}``: PartitionSpec trees
    for the three input groups plus the activation hint policy (sans
    ``__mesh__``, which the engine binds to its concrete slice).
    """
    shape_cfg = ShapeConfig("serve", "decode", 1, 1)   # structure-only
    return {
        "params": param_pspecs(cfg, ax, fsdp=fsdp),
        "cache": cache_pspecs(cfg, ax, shape_cfg, seq_shard=seq_shard,
                              batch_shard=False),
        "batch": batch_pspec(ax, shape_cfg, batch_shard=False),
        "policy": activation_hint_policy(cfg, ax, shape_cfg,
                                         batch_shard=False),
    }
