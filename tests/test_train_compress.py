"""Error-feedback residual as first-class training state.

Covers the bias bug the residual-carry fixes (property test: carried residual
→ strictly lower cumulative error than the residual-dropping variant), the
amax=0 edge case, microbatched metric accumulation, TrainerConfig knob
wiring, and — in subprocesses with a fake 8-device CPU platform — the
compressed-path fault-injection restart (bitwise identical to an
uninterrupted run, residual included) and the elastic pod-count reshard of
the checkpointed residual.
"""

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from _subproc import run_sub as _run_sub

from repro.data import DataConfig
from repro.dist.compression import (
    compressed_psum_mean,
    init_residual,
    reshard_residual,
)
from repro.models import ModelConfig
from repro.optim import AdamWConfig, init_opt_state
from repro.train import Trainer, TrainerConfig, make_train_step


TINY = ModelConfig(name="t", num_layers=2, d_model=32, num_heads=2,
                   num_kv_heads=1, d_ff=64, vocab_size=64,
                   param_dtype="float32", compute_dtype="float32")


# ---------------------------------------------------------------------------
# compressed_psum_mean: residual carry vs residual drop (the fixed bias)
# ---------------------------------------------------------------------------

def _pod_compress(carry_err):
    """vmap-over-pods wrapper: lax collectives bind to the vmapped axis."""
    if carry_err:
        return jax.vmap(
            lambda g, e: compressed_psum_mean(g, "pod", e),
            axis_name="pod", in_axes=(0, 0), out_axes=(0, 0))
    return jax.vmap(lambda g: compressed_psum_mean(g, "pod"),
                    axis_name="pod", in_axes=0, out_axes=(0, 0))


@given(seed=st.integers(0, 10**6))
@settings(max_examples=8, deadline=None)
def test_residual_carry_strictly_reduces_cumulative_error(seed):
    """Carried for K steps, the cumulative compressed mean telescopes to the
    exact cumulative mean (± final residual / n); dropping the residual lets
    per-step rounding bias accumulate linearly.  Per leaf, mean |cumulative
    error| must be *strictly* lower with the carry."""
    K, pods = 12, 4
    rng = np.random.default_rng(seed)
    shapes = {"w": (pods, 6, 5), "b": (pods, 7)}
    # per-pod constant component → the dropped variant's rounding error
    # correlates across steps (the bias regime error feedback exists for)
    base = {k: jnp.asarray(rng.normal(0, 1, s), jnp.float32)
            for k, s in shapes.items()}

    step_cold = jax.jit(_pod_compress(carry_err=False))
    step_carry = jax.jit(_pod_compress(carry_err=True))

    err = jax.tree.map(lambda b: jnp.zeros_like(b), base)
    cum_carry = {k: 0.0 * base[k][0] for k in base}
    cum_drop = {k: 0.0 * base[k][0] for k in base}
    for t in range(K):
        g = {k: base[k] + 0.05 * jnp.asarray(
                 rng.normal(0, 1, shapes[k]), jnp.float32) for k in base}
        exact = {k: jnp.mean(g[k], axis=0) for k in g}
        m_c, err = step_carry(g, err)
        m_d, _ = step_cold(g)
        # every pod's copy of the mean is identical — take pod 0
        cum_carry = {k: cum_carry[k] + m_c[k][0] - exact[k] for k in g}
        cum_drop = {k: cum_drop[k] + m_d[k][0] - exact[k] for k in g}

    for k in base:
        carried = float(jnp.mean(jnp.abs(cum_carry[k])))
        dropped = float(jnp.mean(jnp.abs(cum_drop[k])))
        assert carried < dropped, (k, carried, dropped)


def test_compressed_all_zero_gradients_amax_zero_path():
    """amax=0 must not produce NaN/Inf: mean and residual stay exactly 0."""
    g = {"w": jnp.zeros((4, 8, 3)), "b": jnp.zeros((4, 5))}
    mean, err = jax.jit(_pod_compress(carry_err=False))(g)
    for leaf in jax.tree.leaves(mean) + jax.tree.leaves(err):
        arr = np.asarray(leaf)
        assert np.all(np.isfinite(arr))
        np.testing.assert_array_equal(arr, np.zeros_like(arr))
    # and a second step carrying the (zero) residual stays zero too
    mean2, err2 = jax.jit(_pod_compress(carry_err=True))(g, err)
    np.testing.assert_array_equal(np.asarray(mean2["w"]),
                                  np.zeros_like(np.asarray(mean2["w"])))
    np.testing.assert_array_equal(np.asarray(err2["b"]),
                                  np.zeros_like(np.asarray(err2["b"])))


def test_reshard_residual_preserves_applied_correction():
    rng = np.random.default_rng(0)
    res = {"w": jnp.asarray(rng.normal(0, 1, (2, 3, 4)), jnp.float32)}
    same = reshard_residual(res, 2)
    np.testing.assert_array_equal(np.asarray(same["w"]),
                                  np.asarray(res["w"]))
    up = reshard_residual(res, 4)["w"]
    assert up.shape == (4, 3, 4)
    # Σ'e'/n' == Σe/n: every new pod carries the old pods' mean
    np.testing.assert_allclose(np.asarray(jnp.mean(up, axis=0)),
                               np.asarray(jnp.mean(res["w"], axis=0)),
                               rtol=1e-6)
    down = reshard_residual({"w": up}, 1)["w"]
    assert down.shape == (1, 3, 4)


def test_init_residual_shapes():
    params = {"a": jnp.ones((3, 4)), "n": {"b": jnp.ones(7)}}
    res = init_residual(params, 2)
    assert res["a"].shape == (2, 3, 4)
    assert res["n"]["b"].shape == (2, 7)
    assert all(l.dtype == jnp.float32 for l in jax.tree.leaves(res))


# ---------------------------------------------------------------------------
# microbatched metrics (satellite bugfix: grads_of used to return {})
# ---------------------------------------------------------------------------

def _tiny_state(cfg=TINY, seed=0):
    from repro.models.model import init_params
    ocfg = AdamWConfig(learning_rate=1e-3)
    params = init_params(jax.random.key(seed), cfg)
    opt = init_opt_state(params, ocfg)
    pipe = TokenPipelineBatch()
    return ocfg, params, opt, pipe


class TokenPipelineBatch:
    def __init__(self):
        from repro.data import TokenPipeline
        self.p = TokenPipeline(DataConfig(vocab_size=64, seq_len=32,
                                          global_batch=8))

    def at(self, step):
        return {k: jnp.asarray(v) for k, v in self.p.batch_at(step).items()}


def test_microbatched_step_keeps_ce_metric_and_matches_plain():
    ocfg, params, opt, pipe = _tiny_state()
    step1 = jax.jit(make_train_step(TINY, ocfg))
    step4 = jax.jit(make_train_step(TINY, ocfg, microbatches=4))
    batch = pipe.at(0)
    p1, o1, r1, m1 = step1(params, opt, None, batch)
    p4, o4, r4, m4 = step4(params, opt, None, batch)
    assert r1 is None and r4 is None
    assert "ce" in m1 and "ce" in m4      # used to be dropped under accum
    assert float(m4["ce"]) == pytest.approx(float(m1["ce"]), rel=1e-4)
    assert float(m4["loss"]) == pytest.approx(float(m1["loss"]), rel=1e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_microbatched_moe_aux_metrics_accumulated():
    from repro.models import MoEConfig
    cfg = ModelConfig(name="tm", num_layers=2, d_model=32, num_heads=4,
                      num_kv_heads=2, d_ff=64, vocab_size=64,
                      param_dtype="float32", compute_dtype="float32",
                      moe=MoEConfig(num_experts=4, top_k=2, expert_d_ff=48,
                                    capacity_factor=8.0, layer_period=2,
                                    layer_offset=1))
    ocfg, params, opt, pipe = _tiny_state(cfg)
    step1 = jax.jit(make_train_step(cfg, ocfg))
    step2 = jax.jit(make_train_step(cfg, ocfg, microbatches=2))
    batch = pipe.at(0)
    _, _, _, m1 = step1(params, opt, None, batch)
    _, _, _, m2 = step2(params, opt, None, batch)
    for k in ("ce", "aux_loss", "z_loss", "expert_load"):
        assert k in m1 and k in m2, (k, list(m1), list(m2))
    for k in ("ce", "aux_loss", "z_loss"):   # intensive: per-token means
        np.testing.assert_allclose(np.asarray(m2[k]), np.asarray(m1[k]),
                                   rtol=5e-2, atol=1e-3)
    # expert_load is an extensive token count: summed (not meaned) across
    # microbatches, so the same global batch reports comparable totals
    # whatever the accumulation factor (routing may shift a little because
    # per-microbatch capacity drops go through different boundaries)
    np.testing.assert_allclose(np.asarray(m2["expert_load"]),
                               np.asarray(m1["expert_load"]), rtol=0.3)


# ---------------------------------------------------------------------------
# Trainer knob wiring (satellite bugfix: knobs used to be ignored)
# ---------------------------------------------------------------------------

def _trainer(tmp, total=3, checkpoint_every=10, **kw):
    return Trainer(TINY, AdamWConfig(learning_rate=3e-3),
                   DataConfig(vocab_size=64, seq_len=32, global_batch=8),
                   TrainerConfig(total_steps=total,
                                 checkpoint_every=checkpoint_every,
                                 checkpoint_dir=tmp, log_every=5, **kw))


def test_trainer_microbatches_knob_is_wired(tmp_path):
    p1, _, _ = _trainer(str(tmp_path / "a"), microbatches=1).run()
    p4, _, _ = _trainer(str(tmp_path / "b"), microbatches=4).run()
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_trainer_plain_path_residual_none_roundtrip(tmp_path):
    d = str(tmp_path)
    tr = _trainer(d, total=2, checkpoint_every=2)
    tr.run()
    assert tr.last_residual is None
    tr2 = _trainer(d, total=4, checkpoint_every=2)
    params, opt, residual, start = tr2.init_or_restore()
    assert residual is None and start == 2


def test_trainer_single_pod_mesh_checkpoints_residual(tmp_path):
    """mesh_shape=(1,1) runs the full compressed pod path on one device."""
    d = str(tmp_path)
    tr = _trainer(d, total=4, checkpoint_every=2, mesh_shape=(1, 1),
                  compress_pods=True)
    tr.run()
    saved = tr.last_residual
    assert saved is not None
    assert all(l.shape[0] == 1 for l in jax.tree.leaves(saved))
    # residual really carries information after 4 int8 steps
    assert max(float(jnp.abs(l).max()) for l in jax.tree.leaves(saved)) > 0
    tr2 = _trainer(d, total=6, checkpoint_every=2, mesh_shape=(1, 1),
                   compress_pods=True)
    _, _, restored, start = tr2.init_or_restore()
    assert start == 4
    for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_legacy_checkpoint_without_residual(tmp_path):
    """Pre-residual checkpoints cold-start the error feedback at zero."""
    from repro.checkpoint import Checkpointer
    d = str(tmp_path)
    tr = _trainer(d, total=4, mesh_shape=(1, 1), compress_pods=True)
    params, opt, residual, _ = tr.init_or_restore()
    Checkpointer(d).save(2, {"params": params, "opt": opt}, blocking=True)
    _, _, restored, start = tr.init_or_restore()
    assert start == 2
    for leaf in jax.tree.leaves(restored):
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.zeros_like(np.asarray(leaf)))


# ---------------------------------------------------------------------------
# multi-device: compressed-path restart bitwise + elastic pod reshard
# ---------------------------------------------------------------------------

_SUB_PRELUDE = """
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from repro.models import ModelConfig
    from repro.optim import AdamWConfig
    from repro.data import DataConfig
    from repro.launch.mesh import make_mesh
    from repro.train import Trainer, TrainerConfig

    cfg = ModelConfig(name='t', num_layers=2, d_model=32, num_heads=4,
                      num_kv_heads=2, d_ff=64, vocab_size=64,
                      param_dtype='float32', compute_dtype='float32')

    def mk(d, total, mesh_shape=(2, 2), micro=1):
        return Trainer(cfg, AdamWConfig(learning_rate=3e-3),
                       DataConfig(vocab_size=64, seq_len=32, global_batch=8),
                       TrainerConfig(total_steps=total, checkpoint_every=3,
                                     checkpoint_dir=d, mesh_shape=mesh_shape,
                                     compress_pods=True, microbatches=micro))
"""


def _run_pod_sub(body: str) -> str:
    # dedent the pieces separately: the prelude and body have different
    # indent depths, and a joint dedent would nest the body inside mk()
    return _run_sub(textwrap.dedent(_SUB_PRELUDE) + textwrap.dedent(body))


def test_compressed_restart_bitwise_identical_to_uninterrupted():
    """Crash at step 5 of 8 on the int8 pod path, resume, and match the
    straight-through run bit for bit — params AND residual (the state the
    seed trainer silently dropped)."""
    out = _run_pod_sub("""
        d1, d2 = tempfile.mkdtemp(), tempfile.mkdtemp()
        try:
            mk(d1, 8).run(inject_failure_at=5)
            raise SystemExit('no injected failure?')
        except RuntimeError:
            pass
        ta = mk(d1, 8); pa, _, _ = ta.run()          # resumed
        tb = mk(d2, 8); pb, _, _ = tb.run()          # uninterrupted
        for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(ta.last_residual),
                        jax.tree.leaves(tb.last_residual)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print('OK')
    """)
    assert "OK" in out


def test_compressed_restart_bitwise_with_microbatches():
    out = _run_pod_sub("""
        d1, d2 = tempfile.mkdtemp(), tempfile.mkdtemp()
        try:
            mk(d1, 7, micro=2).run(inject_failure_at=4)
            raise SystemExit('no injected failure?')
        except RuntimeError:
            pass
        pa, _, _ = mk(d1, 7, micro=2).run()
        pb, _, _ = mk(d2, 7, micro=2).run()
        for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print('OK')
    """)
    assert "OK" in out


def test_pod_count_reshard_restores_residual_elastically():
    """Save on 2 pods, restore onto 4 (and back onto 1): residual leaves are
    mean-broadcast (Σe/n preserved), placed P(pod) on the new mesh, and
    training continues."""
    out = _run_pod_sub("""
        d = tempfile.mkdtemp()
        tr2 = mk(d, 4)
        tr2.run()
        want = np.asarray(jax.tree.leaves(tr2.last_residual)[0]).mean(axis=0)
        tr4 = mk(d, 6, mesh_shape=(4, 2))
        p, o, r, start = tr4.init_or_restore()
        assert start == 4
        leaves = jax.tree.leaves(r)
        assert all(l.shape[0] == 4 for l in leaves)
        got = np.asarray(leaves[0])
        for i in range(4):
            np.testing.assert_allclose(got[i], want, rtol=1e-6)
        _, _, hist = tr4.run()
        assert hist, 'no training after reshard'
        tr1 = mk(d, 6, mesh_shape=(1, 2))
        _, _, r1, _ = tr1.init_or_restore()
        assert all(l.shape[0] == 1 for l in jax.tree.leaves(r1))
        print('OK')
    """)
    assert "OK" in out


def test_pod_step_matches_single_device_within_int8_tolerance():
    """The (2,2)-mesh compressed step stays close to the plain single-config
    step (int8 quantization tolerance) — the vmap-over-pods + manual-reduce
    restructuring must not change the math."""
    out = _run_pod_sub("""
        from repro.optim import init_opt_state
        from repro.models.model import init_params
        from repro.train import make_train_step
        from repro.data import TokenPipeline
        ocfg = AdamWConfig(learning_rate=3e-3)
        params = init_params(jax.random.key(0), cfg)
        opt = init_opt_state(params, ocfg)
        batch = {k: jnp.asarray(v) for k, v in TokenPipeline(
            DataConfig(vocab_size=64, seq_len=32, global_batch=8)
        ).batch_at(0).items()}
        plain = jax.jit(make_train_step(cfg, ocfg))
        p_ref, _, _, m_ref = plain(params, opt, None, batch)
        mesh = make_mesh((2, 2), ('pod', 'data'))
        exact = jax.jit(make_train_step(cfg, ocfg, pod_axis='pod',
                                        compress_pods=False, mesh=mesh))
        comp = jax.jit(make_train_step(cfg, ocfg, pod_axis='pod',
                                       compress_pods=True, mesh=mesh))
        with jax.set_mesh(mesh):
            p_ex, _, r_ex, m_ex = exact(params, opt, None, batch)
            p_cp, _, res, m_cp = comp(params, opt, None, batch)
        assert r_ex is None
        assert all(l.shape[0] == 2 for l in jax.tree.leaves(res))
        # exact pod reduce: pure restructuring, must match plain tightly
        assert abs(float(m_ref['loss']) - float(m_ex['loss'])) < 1e-5
        d_ex = max(float(jnp.abs(a - b).max())
                   for a, b in zip(jax.tree.leaves(p_ref),
                                   jax.tree.leaves(p_ex)))
        print('MAXDIFF exact', d_ex)
        assert d_ex < 1e-5
        # int8 path: loss (pre-update) identical; params within the Adam
        # step bound — a quantized near-zero grad can flip m/sqrt(v) by
        # O(1), moving that element by up to ~lr on the first step
        assert abs(float(m_ref['loss']) - float(m_cp['loss'])) < 1e-5
        d_cp = max(float(jnp.abs(a - b).max())
                   for a, b in zip(jax.tree.leaves(p_ref),
                                   jax.tree.leaves(p_cp)))
        print('MAXDIFF int8', d_cp)
        assert d_cp < 2 * 3e-3
        print('OK')
    """)
    assert "OK" in out
