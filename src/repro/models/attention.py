"""Attention blocks: GQA (+ local/global windows, softcap, qk-norm) and MLA.

Design notes
------------
* Prefill/train attention is *chunked* with an online-softmax accumulator
  (flash-attention recurrence in pure JAX): ``lax.scan`` over query chunks,
  ``lax.fori_loop`` over the causally-reachable key chunks.  Peak live memory
  per step is O(q_chunk × k_chunk) instead of O(S²) — required for the 32k
  prefill cells, and it keeps HLO small for the 512-device dry-runs.
  Local-window layers (Gemma-2) additionally lower-bound the key-chunk loop,
  so skipped chunks cost neither FLOPs nor bytes.
* Decode attends one query against the full KV cache (no S² term).
* MLA (DeepSeek-V2) caches only the compressed latent (kv_lora + rope dims)
  and uses the absorbed-projection trick at decode: W_UK folds into the query
  and W_UV into the output, so per-token cache traffic is kv_lora+rope ≈ 576
  values instead of 2·H·head_dim = 32768 — the paper-published 57× KV saving.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.hints import shard_hint
from repro.kernels import paged_attention as paged_kernel
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, dense_init, dtype_of, rms_norm, softcap

NEG = -2.3e38  # practical -inf for f32 masking


# ===========================================================================
# GQA
# ===========================================================================

def init_gqa_params(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg.param_dtype)
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (D, H * hd), dt),
        "wk": dense_init(ks[1], (D, KV * hd), dt),
        "wv": dense_init(ks[2], (D, KV * hd), dt),
        "wo": dense_init(ks[3], (H * hd, D), dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def _qk_chunk_scores(qc_, kc_, scale, cap):
    """qc_: (B,Q,N,G,d) f32-accum scores against kc_: (B,K,N,d)."""
    s = jnp.einsum("bqngd,bknd->bngqk", qc_, kc_,
                   preferred_element_type=jnp.float32) * scale
    return softcap(s, cap) if cap is not None else s


def chunked_causal_attention(
    q: jax.Array,            # (B, S, H, d)
    k: jax.Array,            # (B, S, KV, d)
    v: jax.Array,            # (B, S, KV, d)
    *,
    scale: float,
    attn_cap: float | None,
    window: int | None,      # None → global causal
    q_chunk: int | None = None,
    kv_chunk: int = 512,
    differentiable: bool = False,
) -> jax.Array:
    """Online-softmax chunked attention with decoupled q/kv chunk sizes.

    Two inner-loop flavours:
      * inference (``differentiable=False``): ``fori_loop`` with a dynamic
        upper bound — only causally-reachable key chunks are touched (exact
        triangular FLOPs), but dynamic-bound loops don't reverse-diff;
      * training  (``differentiable=True``): ``scan`` with a *static* trip
        count.  Global layers sweep all key chunks and rely on the causal
        mask (≤2× attention-matmul FLOPs — see §Perf for the custom-vjp
        reclaim); local-window layers keep exact chunk skipping because the
        window span is static.

    Sharding note (§Perf): the default q_chunk=512 pairs with head-sharded
    layouts.  Installing the ``__attn_q_chunk__`` policy key sets q_chunk=S
    (one q block) so the softmax carries shard over *query positions* — the
    only dim guaranteed divisible by the model axis for every assigned arch
    (head counts 8/10/24/56 pad, which makes GSPMD re-gather the carries on
    every inner step).
    """
    from repro.dist.hints import current_policy
    B, S, H, d = q.shape
    KV = k.shape[2]
    dv = v.shape[-1]          # MLA: value head dim ≠ query head dim
    G = H // KV
    pol = current_policy() or {}
    if q_chunk is None:
        q_chunk = pol.get("__attn_q_chunk__", 512)
        if q_chunk == "full":
            q_chunk = S
    qc = min(q_chunk, S)
    kc = min(kv_chunk, S)
    if S % qc or S % kc:
        # Pad to whole chunks and drop the padded rows: every padded key
        # lies after every real query, so the causal mask hides it.
        step = math.lcm(qc, kc)
        pad = ((0, 0), (0, -S % step), (0, 0), (0, 0))
        out = chunked_causal_attention(
            jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad), scale=scale,
            attn_cap=attn_cap, window=window, q_chunk=qc, kv_chunk=kc,
            differentiable=differentiable)
        return out[:, :S]
    nq = S // qc
    nk = S // kc

    qs = jnp.moveaxis(q.reshape(B, nq, qc, KV, G, d), 1, 0)  # (nq,B,qc,KV,G,d)

    def make_step(i, qblk):
        qpos = i * qc + jnp.arange(qc)                        # (qc,)

        def process_chunk(state, j, extra_valid):
            m, l, acc = state
            kblk = lax.dynamic_slice_in_dim(k, j * kc, kc, axis=1)
            vblk = lax.dynamic_slice_in_dim(v, j * kc, kc, axis=1)
            s = _qk_chunk_scores(qblk, kblk, scale, attn_cap)  # (B,KV,G,qc,kc)
            kpos = j * kc + jnp.arange(kc)
            mask = kpos[None, :] <= qpos[:, None]              # causal
            if window is not None:
                mask &= (qpos[:, None] - kpos[None, :]) < window
            mask &= extra_valid
            s = jnp.where(mask[None, None, None], s, NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))        # (B,KV,G,qc)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bngqk,bknd->bngqd", p, vblk,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return m_new, l_new, acc_new

        return qpos, process_chunk

    def q_body(carry, inp):
        i, qblk = inp                                          # qblk (B,qc,KV,G,d)
        _, process_chunk = make_step(i, qblk)
        m0 = jnp.full((B, KV, G, qc), NEG, jnp.float32)
        l0 = jnp.zeros((B, KV, G, qc), jnp.float32)
        a0 = jnp.zeros((B, KV, G, qc, dv), jnp.float32)

        if not differentiable:
            lo = 0 if window is None else \
                jnp.maximum(0, (i * qc - window) // kc)
            hi = ((i + 1) * qc + kc - 1) // kc
            m, l, acc = lax.fori_loop(
                lo, hi,
                lambda j, st: process_chunk(st, j, True), (m0, l0, a0))
        else:
            span = nk if window is None else \
                (window - 1 + qc - 1) // kc + 2   # kv chunks a q block can see
            if window is None or span >= nk:
                R = nk

                def offs_to_j(r):
                    return r, r * kc <= i * qc + qc - 1
            else:
                R = span

                def offs_to_j(r):
                    j_raw = (i * qc - (window - 1)) // kc + r
                    return jnp.clip(j_raw, 0, nk - 1), \
                        (j_raw >= 0) & (j_raw * kc <= i * qc + qc - 1)

            def scan_body(st, r):
                j, valid = offs_to_j(r)
                return process_chunk(st, j, valid), None

            (m, l, acc), _ = lax.scan(scan_body, (m0, l0, a0), jnp.arange(R))

        out = acc / jnp.maximum(l, 1e-30)[..., None]           # (B,KV,G,qc,d)
        return carry, jnp.moveaxis(out, 3, 1)                  # (B,qc,KV,G,d)

    if differentiable:
        # flash-style memory behaviour under autodiff: per-q-chunk remat means
        # the backward holds ONE chunk row of probabilities at a time instead
        # of stacking (B,H,S,S) as scan residuals.
        q_body = jax.checkpoint(
            q_body, policy=jax.checkpoint_policies.nothing_saveable)

    if nq == 1:  # single q block: no outer scan, carries shard on q positions
        _, out_block = q_body(None, (jnp.asarray(0), qs[0]))
        out = out_block.reshape(B, S, H, dv)
        return out.astype(q.dtype)

    _, outs = lax.scan(q_body, None, (jnp.arange(nq), qs))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, dv)        # (B,S,H,dv)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,            # (B, 1, H, d)
    k_cache: jax.Array,      # (B, Smax, KV, d)
    v_cache: jax.Array,      # (B, Smax, KV, d)
    pos: jax.Array,          # () shared position, or (B,) one per sequence
    *,
    scale: float,
    attn_cap: float | None,
    window: int | None,
) -> jax.Array:
    """One-query attention against the cache.

    ``pos`` is the number of valid cache slots: a scalar for lockstep batched
    decode, or a ``(B,)`` vector for continuous batching, where every row of
    the batch sits at its own sequence position (serve/paging.py).  Rows are
    independent either way, so a vector-``pos`` row computes bit-identically
    to the same request decoded alone with a scalar ``pos``.
    """
    B, _, H, d = q.shape
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, d)
    s = _qk_chunk_scores(qg, k_cache, scale, attn_cap)         # (B,KV,G,1,Smax)
    kpos = jnp.arange(Smax)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        mask = kpos <= pos
        if window is not None:
            mask &= (pos - kpos) < window
        s = jnp.where(mask[None, None, None, None, :], s, NEG)
    else:
        mask = kpos[None, :] <= pos[:, None]                   # (B, Smax)
        if window is not None:
            mask &= (pos[:, None] - kpos[None, :]) < window
        s = jnp.where(mask[:, None, None, None, :], s, NEG)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bngqk,bknd->bqngd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, d).astype(q.dtype)


class PagedKV(NamedTuple):
    """One attention layer's keys and values left in the paged decode
    tick's page pool (``serve/paging.py``), in place of a dense cache."""

    k: jax.Array          # (L, num_pages + 1, page_size, KV, d): all layers
    v: jax.Array
    layer: jax.Array      # () int32: this layer's index into ``k``/``v``
    table: jax.Array      # (B, pages_per_slot) int32: each lane's page ids


def paged_decode_attention(q, k_new, v_new, pool_k, pool_v, layer, table,
                           pos, *, scale: float) -> jax.Array:
    """One query per lane against keys ``0..pos``: the lane's cached pages
    of ``layer`` plus its own new key/value (``(B, 1, KV, d)``, not yet in
    the pool).  On a TPU the Pallas kernel reads the live pages in place
    (``kernels/paged_attention.py``); elsewhere this gathers the layer's
    pages into a dense view, writes the new token at ``pos`` and runs
    :func:`decode_attention`, the same operations as a dense cache."""
    if paged_kernel.use_kernel():
        return paged_kernel.paged_decode_attention_kernel(
            q, k_new, v_new, pool_k, pool_v, layer, table, pos, scale=scale)
    return paged_decode_attention_ref(q, k_new, v_new, pool_k, pool_v, layer,
                                      table, pos, scale=scale)


def paged_decode_attention_ref(q, k_new, v_new, pool_k, pool_v, layer, table,
                               pos, *, scale: float) -> jax.Array:
    """:func:`paged_decode_attention` through a dense view of one layer."""
    B, pp = table.shape
    rows = jnp.arange(B)

    def view(pool, new):
        pages = pool[layer][table]                 # (B, pp, ps, KV, d)
        dense = pages.reshape(B, pp * pages.shape[2], *pages.shape[3:])
        return dense.at[rows, pos].set(new[:, 0])

    return decode_attention(q, view(pool_k, k_new), view(pool_v, v_new),
                            pos, scale=scale, attn_cap=None, window=None)


def gqa_block(
    params: dict,
    x: jax.Array,             # (B, S, D)
    cfg: ModelConfig,
    *,
    window: int | None,
    positions: jax.Array,     # (S,) or scalar decode position
    cache: dict | PagedKV | None = None,  # {'k': (B,Smax,KV,d), 'v': ...}
    decode_pos: jax.Array | None = None,
    differentiable: bool = False,
) -> tuple[jax.Array, dict | None]:
    B, S, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = shard_hint((x @ params["wq"]).reshape(B, S, H, hd), "attn_heads")
    k = shard_hint((x @ params["wk"]).reshape(B, S, KV, hd), "attn_heads")
    v = shard_hint((x @ params["wv"]).reshape(B, S, KV, hd), "attn_heads")
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    scale = hd ** -0.5

    if decode_pos is None:
        # §Perf hint: gathering K/V ONCE here (e.g. P(b, None, None, None))
        # replaces a per-kv-chunk re-gather inside the online-softmax scan
        # (with S-sharded K/V each dynamic slice straddles shards and GSPMD
        # gathers the full tensor per step).
        k = shard_hint(k, "attn_kv")
        v = shard_hint(v, "attn_kv")

    new_cache = None
    if isinstance(cache, PagedKV):
        # Paged decode tick: attend in the pool, hand back only the new
        # token's K/V (the tick writes it to its page after the layers).
        assert window is None and cfg.attn_softcap is None and S == 1
        k_new = k.astype(cache.k.dtype)
        v_new = v.astype(cache.v.dtype)
        out = paged_decode_attention(q, k_new, v_new, cache.k, cache.v,
                                     cache.layer, cache.table, decode_pos,
                                     scale=scale)
        new_cache = {"k": k_new[:, 0], "v": v_new[:, 0]}
    elif decode_pos is not None:
        assert cache is not None and S == 1
        if jnp.ndim(decode_pos) == 0:
            k_cache = lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), decode_pos, axis=1)
            v_cache = lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), decode_pos, axis=1)
        else:
            # Continuous batching: each row writes its token at its own
            # position (row-independent scatter — bit-identical per row to
            # the scalar-pos update of that row alone).
            rows = jnp.arange(B)
            k_cache = cache["k"].at[rows, decode_pos].set(
                k[:, 0].astype(cache["k"].dtype))
            v_cache = cache["v"].at[rows, decode_pos].set(
                v[:, 0].astype(cache["v"].dtype))
        out = decode_attention(q, k_cache, v_cache, decode_pos, scale=scale,
                               attn_cap=cfg.attn_softcap, window=window)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        out = chunked_causal_attention(q, k, v, scale=scale,
                                       attn_cap=cfg.attn_softcap, window=window,
                                       differentiable=differentiable)
        if cache is not None:  # prefill: fill the cache
            Smax = cache["k"].shape[1]
            kpad = jnp.zeros_like(cache["k"]).at[:, :S].set(k.astype(cache["k"].dtype))
            vpad = jnp.zeros_like(cache["v"]).at[:, :S].set(v.astype(cache["v"].dtype))
            new_cache = {"k": kpad, "v": vpad}
    y = out.reshape(B, S, H * hd) @ params["wo"]
    return y, new_cache


def gqa_cache_spec(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    dt = dtype_of(cfg.compute_dtype)
    shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(shape, dt),
            "v": jax.ShapeDtypeStruct(shape, dt)}


# ===========================================================================
# MLA (DeepSeek-V2)
# ===========================================================================

def init_mla_params(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg.param_dtype)
    D, H = cfg.d_model, cfg.num_heads
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    p = {
        "w_dkv": dense_init(ks[0], (D, cfg.kv_lora_rank), dt),
        "kv_norm": jnp.zeros((cfg.kv_lora_rank,), jnp.float32),
        "w_kr": dense_init(ks[1], (D, rope_d), dt),
        "w_uk": dense_init(ks[2], (cfg.kv_lora_rank, H, nope), dt),
        "w_uv": dense_init(ks[3], (cfg.kv_lora_rank, H, vd), dt),
        "wo": dense_init(ks[4], (H * vd, D), dt),
    }
    if cfg.q_lora_rank > 0:
        p["w_dq"] = dense_init(ks[5], (D, cfg.q_lora_rank), dt)
        p["q_norm"] = jnp.zeros((cfg.q_lora_rank,), jnp.float32)
        p["w_uq"] = dense_init(ks[6], (cfg.q_lora_rank, H, nope + rope_d), dt)
    else:
        p["wq"] = dense_init(ks[5], (D, H, nope + rope_d), dt)
    return p


def _mla_queries(params, x, cfg, positions):
    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank > 0:
        cq = rms_norm(x @ params["w_dq"], params["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhd->bshd", cq, params["w_uq"])
    else:
        q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
    q = shard_hint(q, "attn_heads")
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_block(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    cache: dict | None = None,   # {'ckv': (B,Smax,R), 'kr': (B,Smax,rope_d)}
    decode_pos: jax.Array | None = None,
    differentiable: bool = False,
    **_unused,
) -> tuple[jax.Array, dict | None]:
    B, S, D = x.shape
    H = cfg.num_heads
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    scale = (nope + rope_d) ** -0.5

    q_nope, q_rope = _mla_queries(params, x, cfg, positions)
    ckv = rms_norm(x @ params["w_dkv"], params["kv_norm"], cfg.norm_eps)  # (B,S,R)
    kr = apply_rope((x @ params["w_kr"])[:, :, None, :], positions,
                    cfg.rope_theta)[:, :, 0, :]                            # (B,S,rope)

    new_cache = None
    if decode_pos is not None:
        assert cache is not None and S == 1
        if jnp.ndim(decode_pos) == 0:
            ckv_c = lax.dynamic_update_slice_in_dim(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), decode_pos, axis=1)
            kr_c = lax.dynamic_update_slice_in_dim(
                cache["kr"], kr.astype(cache["kr"].dtype), decode_pos, axis=1)
        else:
            rows = jnp.arange(B)
            ckv_c = cache["ckv"].at[rows, decode_pos].set(
                ckv[:, 0].astype(cache["ckv"].dtype))
            kr_c = cache["kr"].at[rows, decode_pos].set(
                kr[:, 0].astype(cache["kr"].dtype))
        # absorbed decode: fold W_UK into q, attend in latent space
        q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, params["w_uk"])  # (B,1,H,R)
        s = (jnp.einsum("bshr,btr->bhst", q_abs, ckv_c,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bshd,btd->bhst", q_rope, kr_c,
                          preferred_element_type=jnp.float32)) * scale
        kpos = jnp.arange(ckv_c.shape[1])
        if jnp.ndim(decode_pos) == 0:
            s = jnp.where((kpos <= decode_pos)[None, None, None, :], s, NEG)
        else:
            s = jnp.where(
                (kpos[None, :] <= decode_pos[:, None])[:, None, None, :],
                s, NEG)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        o_lat = jnp.einsum("bhst,btr->bshr", p, ckv_c,
                           preferred_element_type=jnp.float32)       # (B,1,H,R)
        out = jnp.einsum("bshr,rhd->bshd", o_lat.astype(x.dtype), params["w_uv"])
        new_cache = {"ckv": ckv_c, "kr": kr_c}
    else:
        # prefill/train: expand to per-head K/V, reuse the chunked kernel
        k_nope = shard_hint(
            jnp.einsum("bsr,rhd->bshd", ckv, params["w_uk"]), "attn_heads")
        v = shard_hint(
            jnp.einsum("bsr,rhd->bshd", ckv, params["w_uv"]), "attn_heads")
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            kr[:, :, None, :], (B, S, H, rope_d))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = chunked_causal_attention(q, k, v, scale=scale, attn_cap=None,
                                       window=None,
                                       differentiable=differentiable)
        if cache is not None:
            Smax = cache["ckv"].shape[1]
            ckv_c = jnp.zeros_like(cache["ckv"]).at[:, :S].set(ckv.astype(cache["ckv"].dtype))
            kr_c = jnp.zeros_like(cache["kr"]).at[:, :S].set(kr.astype(cache["kr"].dtype))
            new_cache = {"ckv": ckv_c, "kr": kr_c}
    y = out.reshape(B, S, H * vd) @ params["wo"]
    return y, new_cache


def mla_cache_spec(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    dt = dtype_of(cfg.compute_dtype)
    return {"ckv": jax.ShapeDtypeStruct((batch, max_len, cfg.kv_lora_rank), dt),
            "kr": jax.ShapeDtypeStruct((batch, max_len, cfg.qk_rope_head_dim), dt)}
