"""The paged decode attention kernel (``kernels/paged_attention.py``) in
interpret mode against the jnp reference, ``models.attention.
paged_decode_attention_ref`` (what the tick runs off the TPU): it gathers
one layer's pages into a dense view, writes the new token at ``pos`` and
runs ``decode_attention``.

Shapes are small (2 layers, 8 pages a lane of 4 tokens); the lanes sit at
positions 0 (no cached key), 15/16/17 (either side of a page boundary) and
``max_len - 1`` (every page), and one padded lane points at the scratch
page only, as the decode tick pads its lane bucket.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import (pages_per_block,
                                           paged_decode_attention_kernel)
from repro.models.attention import paged_decode_attention_ref

LAYERS, PAGE, PP, HD = 2, 4, 8, 16
POSITIONS = (0, 15, 16, 17, PAGE * PP - 1)


def _case(kv, groups, seed=0):
    """Pool, page table (last lane padded onto the scratch page), positions
    and one decode step's q / new K / new V, all f32."""
    rng = np.random.default_rng(seed)
    lanes = len(POSITIONS) + 1
    pages = (lanes - 1) * PP
    shape = (LAYERS, pages + 1, PAGE, kv, HD)
    pool_k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    table = np.full((lanes, PP), pages, np.int32)          # scratch page
    perm = rng.permutation(pages)
    for b in range(lanes - 1):
        table[b] = perm[b * PP:(b + 1) * PP]
    pos = np.asarray(POSITIONS + (0,), np.int32)
    q = jnp.asarray(rng.normal(size=(lanes, 1, kv * groups, HD)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(lanes, 1, kv, HD)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(lanes, 1, kv, HD)), jnp.float32)
    return q, k_new, v_new, pool_k, pool_v, jnp.asarray(table), jnp.asarray(pos)


@pytest.mark.parametrize("block_pages", [1, 2, None])
@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("kv,groups", [(2, 1), (1, 4)], ids=["mha", "gqa4"])
def test_kernel_matches_reference(kv, groups, layer, block_pages):
    q, k_new, v_new, pool_k, pool_v, table, pos = _case(kv, groups)
    scale = HD ** -0.5
    want = paged_decode_attention_ref(q, k_new, v_new, pool_k, pool_v,
                                      jnp.int32(layer), table, pos,
                                      scale=scale)
    got = paged_decode_attention_kernel(
        q, k_new, v_new, pool_k, pool_v, jnp.int32(layer), table, pos,
        scale=scale, block_pages=block_pages, interpret=True)
    assert got.shape == want.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_lane_at_position_zero_attends_only_to_its_own_token():
    q, k_new, v_new, pool_k, pool_v, table, pos = _case(2, 1)
    got = paged_decode_attention_kernel(
        q, k_new, v_new, pool_k, pool_v, jnp.int32(1), table, pos,
        scale=HD ** -0.5, block_pages=2, interpret=True)
    # Lane 0 and the padded lane have no cached key: softmax over one key.
    for b in (0, len(POSITIONS)):
        np.testing.assert_allclose(np.asarray(got[b, 0]),
                                   np.asarray(v_new[b, 0]),
                                   rtol=1e-6, atol=1e-6)


def test_stale_values_beyond_a_lane_never_reach_its_output():
    """Keys past ``pos`` (the rest of the lane's last page, and pages it has
    not reached) change nothing, whatever they hold."""
    q, k_new, v_new, pool_k, pool_v, table, pos = _case(1, 4)
    kw = dict(scale=HD ** -0.5, block_pages=2, interpret=True)
    base = paged_decode_attention_kernel(q, k_new, v_new, pool_k, pool_v,
                                         jnp.int32(0), table, pos, **kw)
    lane = 1                                 # pos 15: keys 0..14 cached
    page, off = int(table[lane, 3]), 15 % PAGE
    noisy_k = pool_k.at[0, page, off:].set(1e30)
    noisy_v = pool_v.at[0, page, off:].set(1e30)
    for j in range(4, PP):
        noisy_k = noisy_k.at[0, int(table[lane, j])].set(jnp.nan)
        noisy_v = noisy_v.at[0, int(table[lane, j])].set(jnp.nan)
    got = paged_decode_attention_kernel(q, k_new, v_new, noisy_k, noisy_v,
                                        jnp.int32(0), table, pos, **kw)
    np.testing.assert_array_equal(np.asarray(got[lane]),
                                  np.asarray(base[lane]))


def test_block_is_a_power_of_two_of_pages_within_a_sequence():
    # deepseek-7b's pages: 16 tokens x 32 KV heads x 128 x bf16 = 128 KiB.
    assert pages_per_block(16, 32, 128, 2, 64) == 4
    assert pages_per_block(16, 32, 128, 2, 2) == 2
    assert pages_per_block(4, 1, 16, 4, PP) == PP
    assert pages_per_block(64, 64, 128, 4, 64) == 1
