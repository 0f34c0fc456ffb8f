"""Model entry points: init, forward, loss, prefill, decode.

Parameters are plain nested-dict pytrees (no framework): stage parameters are
stacked along a leading ``num_stages`` axis (see transformer.py), embeddings
and head live at the top level.  All entry points are jit/pjit-compatible and
take only arrays + static config.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.hints import shard_hint
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models.config import ModelConfig
from repro.models.layers import dtype_of, embed_init, rms_norm, softcap
from repro.models.transformer import (
    _sublayer_plan,
    apply_stack,
    init_stage,
    init_sublayer,
)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg.param_dtype)
    k_embed, k_first, k_stages, k_head = jax.random.split(key, 4)

    params: dict = {
        "embed": embed_init(k_embed, (cfg.vocab_size, cfg.d_model), dt),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(k_head, (cfg.d_model, cfg.vocab_size), dt)

    first_slot = {"kind": "attn", "window": cfg.window_kind(0), "moe": False}
    first = []
    if cfg.first_dense_layers:
        fks = jax.random.split(k_first, cfg.first_dense_layers)
        for i in range(cfg.first_dense_layers):
            cfg_first = cfg.with_(d_ff=cfg.first_dense_d_ff or cfg.d_ff)
            first.append(init_sublayer(fks[i], cfg_first, first_slot))
    params["first"] = first

    stage_keys = jax.random.split(k_stages, cfg.num_stages)
    params["stages"] = jax.vmap(lambda k: init_stage(k, cfg))(stage_keys)
    return params


def init_params_on(key, cfg: ModelConfig, shardings=None) -> dict:
    """:func:`init_params` as one compiled program whose outputs are born
    in ``shardings`` (a pytree of shardings, or None for the default
    device).  Run eagerly, the init holds each leaf's float32 draw before
    the cast and builds everything on one device; at a 7B model's widths
    that alone can exceed a chip."""
    return jax.jit(init_params, static_argnums=1,
                   out_shardings=shardings)(key, cfg)


def param_shapes(cfg: ModelConfig):
    """Shape pytree without allocating (drives param_count + checkpoints)."""
    shapes = jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg))
    return jax.tree.map(lambda l: l.shape, shapes)


def param_specs(cfg: ModelConfig):
    return jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _embed_lookup(embed, tokens):
    return jnp.take(embed, tokens, axis=0)


def _embed_lookup_fwd(embed, tokens):
    # `embed` rides along as a residual only for its shape/dtype/sharding —
    # it is live across the step anyway (the optimizer reads it).
    return _embed_lookup(embed, tokens), (tokens, embed)


def _embed_lookup_bwd(res, dy):
    tokens, embed = res
    # Scatter-add the cotangent, keeping the (V, D) gradient SHARDED: without
    # the hint GSPMD materializes the full unsharded embedding gradient per
    # device (tens of GB for 100k vocabs) before resharding.
    dembed = jnp.zeros(embed.shape, dy.dtype).at[tokens.reshape(-1)].add(
        dy.reshape(-1, embed.shape[1]))
    dembed = shard_hint(dembed, "embed_grad")
    return dembed.astype(embed.dtype), None


_embed_lookup.defvjp(_embed_lookup_fwd, _embed_lookup_bwd)


def _embed_tokens(params, tokens, cfg: ModelConfig):
    x = _embed_lookup(params["embed"], tokens)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x.astype(dtype_of(cfg.compute_dtype))


def _unembed(params, x, cfg: ModelConfig):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    return softcap(logits, cfg.logit_softcap)


def forward(params, tokens, cfg: ModelConfig, *, caches=None, decode_pos=None,
            remat: bool = True, differentiable: bool = False):
    """tokens (B,S) → (hidden (B,S,D), new_caches, metrics)."""
    B, S = tokens.shape
    x = _embed_tokens(params, tokens, cfg)
    x = shard_hint(x, "layer_boundary")
    if decode_pos is not None:
        if jnp.ndim(decode_pos) == 0:
            positions = jnp.full((S,), decode_pos, dtype=jnp.int32)
        else:
            # Per-row decode positions (continuous batching): (B,) → (B, 1),
            # broadcastable against the (..., S) layout apply_rope expects.
            positions = decode_pos.astype(jnp.int32)[:, None]
    else:
        positions = jnp.arange(S, dtype=jnp.int32)
    x, new_caches, metrics = apply_stack(
        params["stages"], params["first"], x, cfg,
        positions=positions, caches=caches, decode_pos=decode_pos, remat=remat,
        differentiable=differentiable)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, new_caches, metrics


def logits_fn(params, tokens, cfg: ModelConfig, remat: bool = True):
    x, _, metrics = forward(params, tokens, cfg, remat=remat)
    return _unembed(params, x, cfg), metrics


def chunked_cross_entropy(params, hidden, labels, cfg: ModelConfig,
                          chunk: int = 512):
    """Mean next-token CE without materializing (B,S,V) f32 logits.

    Scans over sequence chunks; each step computes (B, chunk, V) logits and
    reduces — peak memory is one chunk of logits (vocab stays shardable).
    """
    B, S, D = hidden.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    h = jnp.moveaxis(hidden.reshape(B, nc, chunk, D), 1, 0)
    y = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)

    def body(total, inp):
        hc, yc = inp
        logits = shard_hint(_unembed(params, hc, cfg), "logits")
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return total + jnp.sum(logz - gold), None

    # remat: the backward recomputes one logit chunk at a time instead of
    # stacking (B, S, V) logits as scan residuals.
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (h, y))
    return total / (B * S)


def loss_fn(params, tokens, labels, cfg: ModelConfig, remat: bool = True):
    hidden, _, metrics = forward(params, tokens, cfg, remat=remat,
                                 differentiable=True)
    ce = chunked_cross_entropy(params, hidden, labels, cfg)
    loss = ce
    if metrics:
        loss = loss + metrics.get("aux_loss", 0.0) + metrics.get("z_loss", 0.0)
    out_metrics = {"ce": ce, **{k: v for k, v in metrics.items()}}
    return loss, out_metrics


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    """ShapeDtypeStruct pytree matching the cache layout of apply_stack."""
    plan = _sublayer_plan(cfg)

    def sub_spec(slot):
        if slot["kind"] == "attn":
            spec = (attn_mod.mla_cache_spec(cfg, batch, max_len)
                    if cfg.attn_type == "mla"
                    else attn_mod.gqa_cache_spec(cfg, batch, max_len))
        else:
            spec = mamba_mod.mamba_cache_spec(cfg, batch)
        return {"mixer": spec}

    def stack(spec):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.num_stages,) + s.shape, s.dtype),
            spec)

    first_slot = {"kind": "attn", "window": cfg.window_kind(0), "moe": False}
    return {
        "first": [sub_spec(first_slot) for _ in range(cfg.first_dense_layers)],
        "stages": {f"sub{j}": stack(sub_spec(plan[j]))
                   for j in range(cfg.period)},
    }


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_specs(cfg, batch, max_len))


# ---------------------------------------------------------------------------
# serving entry points
# ---------------------------------------------------------------------------

def prefill_step(params, tokens, cfg: ModelConfig, max_len: int | None = None,
                 differentiable: bool = False):
    """tokens (B,S) → (last-token logits (B,V), filled caches).

    ``differentiable=True`` selects the static-trip-count attention loops
    (used by the dry-run so HLO while bounds are statically analyzable)."""
    B, S = tokens.shape
    caches = init_cache(cfg, B, max_len or S)
    hidden, new_caches, _ = forward(params, tokens, cfg, caches=caches,
                                    remat=False,
                                    differentiable=differentiable)
    logits = _unembed(params, hidden[:, -1:, :], cfg)[:, 0, :]
    return logits, new_caches


def decode_step(params, caches, tokens, pos, cfg: ModelConfig):
    """One decode step.  tokens (B,1); pos: scalar index of this token, or a
    per-row (B,) int32 vector when rows decode at independent positions
    (continuous batching).  Rows are independent, so the vector path is
    bitwise identical per row to running that row alone with a scalar pos.

    Returns (logits (B,V), new_caches).
    """
    hidden, new_caches, _ = forward(params, tokens, cfg, caches=caches,
                                    decode_pos=pos, remat=False)
    logits = _unembed(params, hidden[:, -1:, :], cfg)[:, 0, :]
    return logits, new_caches


# ---------------------------------------------------------------------------
# FLOP accounting (roofline §: MODEL_FLOPS = 6·N·D dense / 6·N_active·D MoE)
# ---------------------------------------------------------------------------

def model_flops(cfg: ModelConfig, tokens: int, *, train: bool = True,
                active_only: bool = True) -> float:
    n = cfg.active_param_count() if active_only else cfg.param_count()
    mult = 6.0 if train else 2.0
    return mult * n * tokens
