"""Paged decode attention on the TPU: one query per lane over its live pages.

The paged decode tick (``serve/paging.py``) keeps every request's keys and
values in a page pool ``(L, num_pages + 1, page_size, KV, hd)`` that holds
all layers.  This kernel attends one new query per lane to keys ``0..pos``
straight from that pool: the pool stays in HBM, and only the pages that hold
a lane's cached keys (``ceil(pos / page_size)`` of them) are copied into
VMEM, a block of pages at a time, double-buffered across blocks and lanes.
An f32 online softmax runs across the blocks, and the lane's own new key and
value (not yet in the pool) are folded in last.

Scores and softmax are f32 over bf16 keys and values, as in
``models.attention.decode_attention``; only the order of the sums differs,
so the result matches that reference to f32 rounding, not bit for bit.

The work is elementwise (VPU): for each key and head, a 128-lane product
and sum with the query, then the probability-weighted sum of the values.
One query row per head would leave the matrix unit nearly idle, and this
keeps the pool's token-major layout, which admission and snapshots use.
GQA groups (``G = H // KV`` query heads per KV head) share each block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -2.3e38  # practical -inf for f32 masking (as models.attention)

# Bytes of K a block of pages should hold: big enough that each block's
# copies stream at HBM bandwidth, small enough that its f32 temporaries fit
# the default scoped VMEM.
_BLOCK_BYTES = 512 * 1024


def use_kernel() -> bool:
    """Whether the paged tick attends through the Pallas kernel (on a TPU)
    rather than the jnp reference (everywhere else)."""
    return jax.default_backend() == "tpu"


def pages_per_block(page_size: int, kv: int, hd: int, itemsize: int,
                    pages_per_slot: int) -> int:
    """Pages copied per block: a power of two near ``_BLOCK_BYTES`` of K,
    at most a whole sequence."""
    page_bytes = page_size * kv * hd * itemsize
    n = 1
    while 2 * n * page_bytes <= _BLOCK_BYTES and 2 * n <= pages_per_slot:
        n *= 2
    return n


def _kernel(layer_ref, table_ref, pos_ref,            # scalar prefetch
            q_ref, kn_ref, vn_ref, pool_k, pool_v,      # inputs
            o_ref,                                      # output
            kbuf, vbuf, sem, slot_ref,                  # scratch
            *, scale, ppb, ps, pp, groups, lanes):
    b = pl.program_id(0)
    layer = layer_ref[0]
    bk = ppb * ps

    def live(lane):                    # pages holding keys 0..pos-1
        return pl.cdiv(pos_ref[lane], ps)

    def blocks(lane):                  # at least one: a block may be empty
        return jnp.maximum(pl.cdiv(live(lane), ppb), 1)

    def copies(lane, blk, slot, fn):
        """Start (or wait for) the copies of one block of ``lane``'s pages
        into buffer ``slot``; pages past the lane's live ones are skipped."""
        n_live = live(lane)
        for i in range(ppb):
            j = blk * ppb + i

            @pl.when(j < n_live)
            def _():
                page = table_ref[lane * pp + j]
                fn(pltpu.make_async_copy(pool_k.at[layer, page],
                                         kbuf.at[slot, i], sem.at[0, slot]))
                fn(pltpu.make_async_copy(pool_v.at[layer, page],
                                         vbuf.at[slot, i], sem.at[1, slot]))

    def start(c):
        c.start()

    def wait(c):
        c.wait()

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0
        copies(0, 0, 0, start)

    pos = pos_ref[b]
    n_blk = blocks(b)
    first = slot_ref[0]
    kv, hd = kbuf.shape[3], kbuf.shape[4]
    q = q_ref[0].astype(jnp.float32)                     # (G, KV, hd)

    def body(blk, carry):
        slot = (first + blk) % 2

        @pl.when(blk + 1 < n_blk)
        def _():
            copies(b, blk + 1, 1 - slot, start)

        @pl.when((blk + 1 == n_blk) & (b + 1 < lanes))
        def _():
            copies(b + 1, 0, 1 - slot, start)

        copies(b, blk, slot, wait)
        k = kbuf[slot].reshape(bk, kv, hd).astype(jnp.float32)
        v = vbuf[slot].reshape(bk, kv, hd).astype(jnp.float32)
        kpos = blk * bk + lax.broadcasted_iota(jnp.int32, (bk, kv, 1), 0)
        mask = kpos < pos
        out = []
        for g, (m, l, acc) in enumerate(carry):
            s = jnp.sum(k * q[g][None], axis=-1, keepdims=True) * scale
            s = jnp.where(mask, s, NEG)                  # (bk, KV, 1)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m - m_new)                    # (1, KV, 1)
            # Pages not copied hold stale or unset VMEM: select, never
            # multiply, so a NaN there cannot reach the sum.
            pv = jnp.sum(jnp.where(mask, p * v, 0.0), axis=0)
            out.append((m_new, l * corr + jnp.sum(p, axis=0, keepdims=True),
                        acc * corr[0] + pv))
        return tuple(out)

    init = tuple((jnp.full((1, kv, 1), NEG, jnp.float32),
                  jnp.zeros((1, kv, 1), jnp.float32),
                  jnp.zeros((kv, hd), jnp.float32)) for _ in range(groups))
    carry = lax.fori_loop(0, n_blk, body, init)
    slot_ref[0] = (first + n_blk) % 2

    # The lane's own token, at ``pos``: always attended, so the running max
    # ends finite and blocks with no live key contribute exactly nothing.
    kn = kn_ref[0].astype(jnp.float32)                   # (KV, hd)
    vn = vn_ref[0].astype(jnp.float32)
    for g, (m, l, acc) in enumerate(carry):
        s = jnp.sum(kn * q[g], axis=-1, keepdims=True)[None] * scale
        m_new = jnp.maximum(m, s)                        # (1, KV, 1)
        corr, p = jnp.exp(m - m_new), jnp.exp(s - m_new)
        l_new = l * corr + p
        o_ref[0, g] = ((acc * corr[0] + p[0] * vn) / l_new[0]).astype(
            o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_pages", "interpret"))
def paged_decode_attention_kernel(q, k_new, v_new, pool_k, pool_v, layer,
                                  table, pos, *, scale: float,
                                  block_pages: int | None = None,
                                  interpret: bool = False):
    """One query per lane against keys ``0..pos`` of that lane: its cached
    pages of layer ``layer`` in ``pool_k``/``pool_v`` and its new token.

    q (B, 1, H, hd); k_new, v_new (B, 1, KV, hd) in the pool's dtype;
    pool_k, pool_v (L, num_pages + 1, page_size, KV, hd); layer () int32;
    table (B, pages_per_slot) int32 page ids; pos (B,) int32, the position
    each lane's new token takes.  Returns (B, 1, H, hd) in ``q.dtype``.
    ``block_pages``: pages copied per block (default
    :func:`pages_per_block`).
    """
    B, _, H, hd = q.shape
    _, _, ps, KV, _ = pool_k.shape
    G = H // KV
    pp = table.shape[1]
    ppb = block_pages or pages_per_block(ps, KV, hd, pool_k.dtype.itemsize,
                                         pp)
    # Query heads are ordered (KV, G): group-major for the kernel.
    qg = q.reshape(B, KV, G, hd).transpose(0, 2, 1, 3)

    def lane3(b, *_):
        return b, 0, 0

    def lane4(b, *_):
        return b, 0, 0, 0

    kernel = functools.partial(_kernel, scale=scale, ppb=ppb, ps=ps, pp=pp,
                               groups=G, lanes=B)
    buf = pltpu.VMEM((2, ppb, ps, KV, hd), pool_k.dtype)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, G, KV, hd), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, G, KV, hd), lane4),
                pl.BlockSpec((1, KV, hd), lane3),
                pl.BlockSpec((1, KV, hd), lane3),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, G, KV, hd), lane4),
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32)],
        ),
        # Lanes run in order: each lane's last block prefetches the next
        # lane's first, through the buffer slot kept in SMEM.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      table.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      qg, k_new[:, 0], v_new[:, 0], pool_k, pool_v)
    return out.transpose(0, 2, 1, 3).reshape(B, 1, H, hd).astype(q.dtype)
