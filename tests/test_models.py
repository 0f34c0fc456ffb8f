"""Per-architecture smoke tests (reduced configs) + model-level equivalences.

Assignment requirement: for each of the 10 architectures, instantiate a
REDUCED config of the same family and run one forward/train step on CPU
asserting output shapes + no NaNs.  Full configs are exercised only via the
dry-run (ShapeDtypeStruct, no allocation).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_arch_names, get_config, get_smoke_config
from repro.models import (
    ModelConfig,
    MoEConfig,
    SSMConfig,
    decode_step,
    init_params,
    logits_fn,
    loss_fn,
    prefill_step,
)
from repro.models.mamba import init_mamba_params, mamba_block, selective_scan
from repro.models.moe import init_moe_params, moe_block


@pytest.mark.parametrize("arch", all_arch_names())
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.key(0)
    params = init_params(key, cfg)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab_size)

    logits, _ = logits_fn(params, toks, cfg)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"

    loss, metrics = loss_fn(params, toks, labels, cfg)
    assert jnp.isfinite(loss)
    assert float(loss) > 0

    grads = jax.grad(lambda p: loss_fn(p, toks, labels, cfg)[0])(params)
    gsum = sum(float(jnp.abs(l).sum()) for l in jax.tree.leaves(grads))
    assert np.isfinite(gsum) and gsum > 0, f"{arch}: bad grads"


@pytest.mark.parametrize("arch", all_arch_names())
def test_smoke_decode_matches_forward(arch):
    """prefill + token-by-token decode == full forward (last-token logits)."""
    cfg = get_smoke_config(arch)
    if cfg.moe is not None:  # avoid capacity-drop divergence in the tiny regime
        cfg = cfg.with_(moe=MoEConfig(**{
            **cfg.moe.__dict__, "capacity_factor": 16.0}))
    params = init_params(jax.random.key(0), cfg)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    logits_full, _ = logits_fn(params, toks, cfg)
    lg, caches = prefill_step(params, toks[:, : S // 2], cfg, max_len=S)
    for t in range(S // 2, S):
        lg, caches = decode_step(params, caches, toks[:, t:t + 1],
                                 jnp.int32(t), cfg)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(logits_full[:, -1]),
                               rtol=5e-3, atol=5e-3)


def test_full_configs_match_published_sizes():
    expected = {
        "musicgen_medium": (1.37e9, 0.03), "deepseek_7b": (6.9e9, 0.03),
        "phi3_medium_14b": (14.7e9, 0.03), "gemma2_9b": (9.2e9, 0.03),
        "yi_34b": (34.4e9, 0.03), "deepseek_v2_236b": (235.7e9, 0.03),
        "arctic_480b": (476.9e9, 0.03), "falcon_mamba_7b": (7.3e9, 0.03),
        "jamba_v0_1_52b": (51.6e9, 0.03), "chameleon_34b": (34.3e9, 0.03),
    }
    for arch, (n, tol) in expected.items():
        cfg = get_config(arch)
        assert cfg.param_count() == pytest.approx(n, rel=tol), arch


def test_moe_active_params_much_smaller():
    for arch in ["deepseek_v2_236b", "arctic_480b", "jamba_v0_1_52b"]:
        cfg = get_config(arch)
        assert cfg.active_param_count() < 0.3 * cfg.param_count()


# ---------------------------------------------------------------------------
# mamba: chunked scan == sequential recurrence oracle
# ---------------------------------------------------------------------------

def _mamba_cfg(chunk):
    return ModelConfig(
        name="m", num_layers=1, d_model=32, num_heads=1, num_kv_heads=1,
        d_ff=0, vocab_size=7, block_pattern=("mamba",),
        ssm=SSMConfig(d_inner=64, d_state=8, chunk=chunk, dt_rank=4),
        param_dtype="float32", compute_dtype="float32")


def test_mamba_chunked_equals_sequential():
    cfg16 = _mamba_cfg(16)
    cfg1 = _mamba_cfg(1)   # chunk=1 → pure sequential recurrence
    params = init_mamba_params(jax.random.key(0), cfg16)
    u = jax.random.normal(jax.random.key(1), (2, 32, 64))
    y16, h16 = selective_scan(params, u, cfg16)
    y1, h1 = selective_scan(params, u, cfg1)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h16), np.asarray(h1),
                               rtol=1e-5, atol=1e-5)


def test_mamba_streaming_equals_batch():
    """Processing a sequence in two halves with carried state == one shot."""
    cfg = _mamba_cfg(4)
    params = init_mamba_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    y_full, _ = mamba_block(params, x, cfg)
    B = 2
    cache = {"conv": jnp.zeros((B, 3, 64)), "ssm": jnp.zeros((B, 64, 8))}
    y1, cache = mamba_block(params, x[:, :8], cfg, cache=cache)
    ys = [y1]
    for t in range(8, 16):
        yt, cache = mamba_block(params, x[:, t:t + 1], cfg, cache=cache,
                                decode_pos=jnp.int32(t))
        ys.append(yt)
    y_inc = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_inc),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# MoE properties
# ---------------------------------------------------------------------------

def _moe_cfg(cf=16.0, experts=8, k=2):
    return ModelConfig(
        name="moe", num_layers=1, d_model=32, num_heads=1, num_kv_heads=1,
        d_ff=64, vocab_size=7,
        moe=MoEConfig(num_experts=experts, top_k=k, expert_d_ff=48,
                      capacity_factor=cf),
        param_dtype="float32", compute_dtype="float32")


def test_moe_no_drops_at_high_capacity():
    cfg = _moe_cfg(cf=32.0)
    params = init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    out, metrics = moe_block(params, x, cfg)
    assert out.shape == x.shape
    # all T·k assignments kept
    assert int(metrics["expert_load"].sum()) == 2 * 16 * cfg.moe.top_k


def test_moe_load_conserved_with_drops():
    cfg = _moe_cfg(cf=0.5)
    params = init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    out, metrics = moe_block(params, x, cfg)
    total = int(metrics["expert_load"].sum())
    assert 0 < total <= 2 * 16 * cfg.moe.top_k
    assert bool(jnp.isfinite(out).all())


def test_moe_aux_losses_finite_positive():
    cfg = _moe_cfg()
    params = init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    _, metrics = moe_block(params, x, cfg)
    assert float(metrics["aux_loss"]) > 0
    assert float(metrics["z_loss"]) >= 0


# ---------------------------------------------------------------------------
# attention variants (windows, softcap) — already covered by arch smokes;
# extra: local window masks really restrict context.
# ---------------------------------------------------------------------------

def test_local_window_changes_long_range_attention():
    base = dict(num_layers=2, d_model=32, num_heads=2, num_kv_heads=1,
                d_ff=64, vocab_size=11, param_dtype="float32",
                compute_dtype="float32")
    cfg_local = ModelConfig(name="loc", window_pattern=("local",),
                            local_window=4, **base)
    cfg_global = ModelConfig(name="glob", **base)
    params = init_params(jax.random.key(0), cfg_local)
    toks = jax.random.randint(jax.random.key(1), (1, 32), 0, 11)
    l_loc, _ = logits_fn(params, toks, cfg_local)
    l_glob, _ = logits_fn(params, toks, cfg_global)
    assert not np.allclose(np.asarray(l_loc[:, -1]), np.asarray(l_glob[:, -1]),
                           atol=1e-5)


@pytest.mark.parametrize("differentiable", [False, True])
@pytest.mark.parametrize("window", [None, 5])
def test_chunked_attention_at_lengths_that_are_not_whole_chunks(
        window, differentiable):
    """A sequence longer than a chunk but not a whole number of chunks (a
    543-token prompt against 512-token chunks) equals plain masked softmax
    attention."""
    from repro.models.attention import chunked_causal_attention

    S, H, KV, d = 19, 4, 2, 8
    q, k, v = (jax.random.normal(jax.random.key(i), (2, S, n, d))
               for i, n in enumerate((H, KV, KV)))
    out = chunked_causal_attention(q, k, v, scale=d ** -0.5, attn_cap=None,
                                   window=window, q_chunk=8, kv_chunk=4,
                                   differentiable=differentiable)
    kr, vr = (jnp.repeat(x, H // KV, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * d ** -0.5
    qpos, kpos = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask &= qpos - kpos < window
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, vr)
    assert out.shape == want.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
