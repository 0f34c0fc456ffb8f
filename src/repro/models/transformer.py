"""Decoder stacks: period-based scan-over-layers with heterogeneous blocks.

Layers are grouped into *stages* of ``cfg.period`` sub-layers (the repeating
``block_pattern``); stage parameters are stacked along a leading axis and the
stack runs as ONE ``lax.scan`` — HLO size is O(period), not O(num_layers),
which keeps 512-device dry-run compiles fast, and remat applies per stage.

Heterogeneity handled here:
  * gemma2: ('local','global') window alternation + sandwich (post) norms;
  * jamba: ('mamba',…,'attn',…) 1:7 pattern with MoE on every 2nd layer;
  * deepseek-v2: first dense layer outside the scan (``first_dense_layers``).

Each sub-layer slot carries its own kind ('attn'|'mamba'), window kind, and
FFN kind ('dense'|'moe'), resolved *statically* from the config at trace time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.hints import shard_hint
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models.config import ModelConfig
from repro.models.ffn import ffn_block, init_ffn_params
from repro.models.layers import rms_norm
from repro.models.moe import init_moe_params, moe_block


def _sublayer_plan(cfg: ModelConfig) -> list[dict]:
    """Static description of each sub-layer slot within a stage."""
    plan = []
    for j in range(cfg.period):
        layer = cfg.first_dense_layers + j  # representative layer index
        kind = cfg.layer_kind(layer)
        moe = cfg.is_moe_layer(layer)
        plan.append({
            "kind": kind,
            "window": cfg.window_kind(layer) if kind == "attn" else None,
            "moe": moe,
            # pure-SSM stacks (falcon-mamba) have no FFN sub-block at all
            "ffn": "moe" if moe else ("none" if cfg.d_ff == 0 else "dense"),
        })
    # sanity: the pattern must align stage-invariantly for window/moe cycles
    for stage in range(1, cfg.num_stages):
        for j in range(cfg.period):
            layer = cfg.first_dense_layers + stage * cfg.period + j
            kind = cfg.layer_kind(layer)
            assert kind == plan[j]["kind"]
            assert cfg.is_moe_layer(layer) == plan[j]["moe"], (
                f"{cfg.name}: MoE period must align with block pattern period")
            if kind == "attn":
                assert cfg.window_kind(layer) == plan[j]["window"], (
                    f"{cfg.name}: window pattern must align with stage period")
    return plan


def init_sublayer(key, cfg: ModelConfig, slot: dict) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: dict = {"norm_1": jnp.zeros((cfg.d_model,), jnp.float32)}
    if slot["kind"] == "attn":
        p["mixer"] = (attn_mod.init_mla_params(k1, cfg) if cfg.attn_type == "mla"
                      else attn_mod.init_gqa_params(k1, cfg))
    else:
        p["mixer"] = mamba_mod.init_mamba_params(k1, cfg)
    ffn_kind = slot.get("ffn", "moe" if slot["moe"] else "dense")
    if ffn_kind != "none":
        p["norm_2"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["ffn"] = (init_moe_params(k2, cfg) if ffn_kind == "moe"
                    else init_ffn_params(k3, cfg))
    if cfg.post_block_norm:
        p["post_norm_1"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["post_norm_2"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return p


def init_stage(key, cfg: ModelConfig) -> dict:
    plan = _sublayer_plan(cfg)
    keys = jax.random.split(key, cfg.period)
    return {f"sub{j}": init_sublayer(keys[j], cfg, plan[j])
            for j in range(cfg.period)}


def apply_sublayer(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    slot: dict,
    *,
    positions,
    cache: dict | None,
    decode_pos,
    differentiable: bool = False,
) -> tuple[jax.Array, dict | None, dict]:
    """Pre-norm residual block: x + mixer(norm(x)); x + ffn(norm(x))."""
    metrics: dict = {}
    h = rms_norm(x, params["norm_1"], cfg.norm_eps)
    # SP-style sequence gather point: with layer-boundary activations sharded
    # (batch, model-on-seq), installing sublayer_input = P(batch, None, None)
    # turns the per-matmul weight gathers over 'model' into ONE activation
    # all-gather here + a reduce-scatter at the boundary (§Perf lever).
    h = shard_hint(h, "sublayer_input")
    if slot["kind"] == "attn":
        window = cfg.local_window if slot["window"] == "local" else None
        block = attn_mod.mla_block if cfg.attn_type == "mla" else attn_mod.gqa_block
        mixer_cache = cache.get("mixer") if cache else None
        with jax.named_scope("attention"):
            h, new_mixer_cache = block(params["mixer"], h, cfg, window=window,
                                       positions=positions, cache=mixer_cache,
                                       decode_pos=decode_pos,
                                       differentiable=differentiable)
    else:
        mixer_cache = cache.get("mixer") if cache else None
        with jax.named_scope("mamba"):
            h, new_mixer_cache = mamba_mod.mamba_block(
                params["mixer"], h, cfg, cache=mixer_cache,
                decode_pos=decode_pos)
    if cfg.post_block_norm:
        h = rms_norm(h, params["post_norm_1"], cfg.norm_eps)
    x = x + h

    ffn_kind = slot.get("ffn", "moe" if slot["moe"] else "dense")
    if ffn_kind != "none":
        h = rms_norm(x, params["norm_2"], cfg.norm_eps)
        h = shard_hint(h, "sublayer_input")
        with jax.named_scope("ffn"):
            if ffn_kind == "moe":
                h, moe_metrics = moe_block(params["ffn"], h, cfg)
                metrics.update(moe_metrics)
            else:
                h = ffn_block(params["ffn"], h, cfg)
        if cfg.post_block_norm:
            h = rms_norm(h, params["post_norm_2"], cfg.norm_eps)
        x = x + h
    x = shard_hint(x, "layer_boundary")

    new_cache = {"mixer": new_mixer_cache} if new_mixer_cache is not None else None
    return x, new_cache, metrics


def apply_stack(
    stage_params: dict,          # leaves stacked (num_stages, ...)
    first_dense_params: list,    # unrolled leading layers (deepseek-v2)
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions,
    caches: dict | None = None,  # {'first': [...], 'stages': stacked pytree}
    decode_pos=None,
    remat: bool = True,
    differentiable: bool = False,
) -> tuple[jax.Array, dict | None, dict]:
    plan = _sublayer_plan(cfg)

    # --- leading dense layers, unrolled -----------------------------------
    first_slot = {"kind": "attn", "window": cfg.window_kind(0), "moe": False}
    new_first_caches = []
    for i, lp in enumerate(first_dense_params):
        c = caches["first"][i] if caches else None
        x, nc, _ = apply_sublayer(lp, x, cfg, first_slot, positions=positions,
                                  cache=c, decode_pos=decode_pos,
                                  differentiable=differentiable)
        new_first_caches.append(nc)

    # --- scanned stages -----------------------------------------------------
    agg_init = {}
    if cfg.moe is not None and any(s["moe"] for s in plan):
        E = cfg.moe.num_experts
        agg_init = {"aux_loss": jnp.zeros((), jnp.float32),
                    "z_loss": jnp.zeros((), jnp.float32),
                    "expert_load": jnp.zeros((E,), jnp.float32)}

    def stage_body(carry, stage_in):
        x, agg = carry
        # barrier: keeps the saved-for-backward residual in its storage dtype
        # (XLA otherwise hoists downstream f32 converts into the save loop,
        # doubling the stacked-residual footprint).  Its VJP barriers the
        # cotangent too, protecting the backward residual stream.
        x = lax.optimization_barrier(x)
        sp, c, layer = stage_in
        new_cache = {}
        for j, slot in enumerate(plan):
            if f"sub{j}" in in_place:
                paged = in_place[f"sub{j}"]["mixer"]._replace(layer=layer)
                sub_cache = {"mixer": paged}
            else:
                sub_cache = c.get(f"sub{j}") if c is not None else None
            x, nc, met = apply_sublayer(sp[f"sub{j}"], x, cfg, slot,
                                        positions=positions, cache=sub_cache,
                                        decode_pos=decode_pos,
                                        differentiable=differentiable)
            if nc is not None:
                new_cache[f"sub{j}"] = nc
            if met:
                agg = {
                    "aux_loss": agg["aux_loss"] + met["aux_loss"],
                    "z_loss": agg["z_loss"] + met["z_loss"],
                    "expert_load": agg["expert_load"] + met["expert_load"],
                }
        return (x, agg), (new_cache if new_cache else None)

    body = stage_body
    if remat:
        body = jax.checkpoint(stage_body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    # Paged decode: a sublayer whose cache is a ``PagedKV`` attends in the
    # page pool, which holds every layer.  Its pool stays out of the scan's
    # xs (loop-invariant: nothing slices or copies it per layer), each
    # layer gets its index from the scan, and the scan stacks only the new
    # token's K/V, (num_stages, B, KV, d).
    in_place = {}
    if caches is not None:
        in_place = {n: c for n, c in caches["stages"].items()
                    if isinstance(c.get("mixer"), attn_mod.PagedKV)}
    if caches is None:
        # scan needs a concrete xs pytree; feed params only
        (x, agg), _ = lax.scan(lambda c, sp: body(c, (sp, None, None)),
                               (x, agg_init), stage_params)
        new_stage_caches = None
    else:
        scanned = {n: c for n, c in caches["stages"].items()
                   if n not in in_place}
        layers = jnp.arange(cfg.num_stages, dtype=jnp.int32)
        (x, agg), new_stage_caches = lax.scan(
            body, (x, agg_init), (stage_params, scanned, layers))

    new_caches = None
    if caches is not None:
        new_caches = {"first": new_first_caches, "stages": new_stage_caches}
    return x, new_caches, agg
