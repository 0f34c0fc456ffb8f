"""Distribution tests: sharding rules, multi-device execution, compression.

Multi-device cases run in subprocesses with a fake 8-device CPU platform
(device count locks at backend init, so the main test process stays at 1)
and EXECUTE real sharded steps — numerics must match the single-device run.
"""

import numpy as np
import pytest

from _subproc import run_sub as _run_sub

from repro.configs import all_arch_names, get_config
from repro.dist.sharding import (
    MeshAxes,
    activation_hint_policy,
    cache_pspecs,
    opt_pspecs,
    param_pspecs,
)
from repro.models.config import SHAPES
from repro.models.model import param_specs


# ---------------------------------------------------------------------------
# spec construction (no devices needed)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", all_arch_names())
def test_param_specs_cover_every_leaf(arch):
    import jax
    from jax.sharding import PartitionSpec
    cfg = get_config(arch)
    ax = MeshAxes(pod="pod")
    specs = param_pspecs(cfg, ax)
    shapes = param_specs(cfg)

    # structure-checked elementwise zip: raises if trees mismatch
    def check(sh, sp):
        assert isinstance(sp, PartitionSpec), (sh, sp)
        assert len(tuple(sp)) <= len(sh.shape), (sp, sh.shape)
        return 0

    jax.tree.map(check, shapes, specs,
                 is_leaf=lambda x: isinstance(x, PartitionSpec))


@pytest.mark.parametrize("arch", ["gemma2_9b", "jamba_v0_1_52b",
                                  "deepseek_v2_236b", "falcon_mamba_7b"])
@pytest.mark.parametrize("shape", ["train_4k", "decode_32k", "long_500k"])
def test_cache_and_policy_specs_build(arch, shape):
    cfg = get_config(arch)
    ax = MeshAxes()
    sc = SHAPES[shape]
    pol = activation_hint_policy(cfg, ax, sc)
    assert "layer_boundary" in pol
    if shape != "train_4k":
        specs = cache_pspecs(cfg, ax, sc)
        import jax
        assert len(jax.tree.leaves(specs,
                                   is_leaf=lambda x: hasattr(x, "index"))) > 0


def test_opt_pspecs_int8_structure():
    cfg = get_config("deepseek_7b")
    ax = MeshAxes()
    ps = param_pspecs(cfg, ax)
    shapes = param_specs(cfg)
    o = opt_pspecs(ps, "int8", ax, param_shapes=shapes)
    assert "q" in o["m"]["embed"] and "scale" in o["m"]["embed"]


# ---------------------------------------------------------------------------
# multi-device execution (subprocess, 8 fake devices)
# ---------------------------------------------------------------------------

def test_sharded_train_step_matches_single_device():
    """Tiny MoE+attention model: 2×2×2 mesh (pod,data,model) pod-compressed
    step ≈ single-device step (int8 gradient compression tolerance)."""
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.models import ModelConfig, MoEConfig, init_params, loss_fn
        from repro.optim import AdamWConfig, adamw_update, init_opt_state
        from repro.dist.sharding import MeshAxes, param_pspecs, activation_hint_policy
        from repro.dist.hints import sharding_policy
        from repro.launch.mesh import make_mesh
        from repro.models.config import ShapeConfig

        cfg = ModelConfig(name='t', num_layers=2, d_model=32, num_heads=4,
                          num_kv_heads=2, d_ff=64, vocab_size=64,
                          param_dtype='float32', compute_dtype='float32',
                          moe=MoEConfig(num_experts=4, top_k=2, expert_d_ff=48,
                                        capacity_factor=8.0, layer_period=2,
                                        layer_offset=1))
        ocfg = AdamWConfig(learning_rate=1e-3)
        key = jax.random.key(0)
        params = init_params(key, cfg)
        opt = init_opt_state(params, ocfg)
        toks = jax.random.randint(jax.random.key(1), (8, 32), 0, 64)
        labels = jax.random.randint(jax.random.key(2), (8, 32), 0, 64)

        def step(p, o, t, l):
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, t, l, cfg)
            p, o, _ = adamw_update(g, o, p, ocfg)
            return p, loss

        # single device reference
        p_ref, loss_ref = jax.jit(step)(params, opt, toks, labels)

        # 8-device mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        ax = MeshAxes(pod="pod")
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           param_pspecs(cfg, ax),
                           is_leaf=lambda x: isinstance(x, P))
        shape_cfg = ShapeConfig('train_4k', 'train', 32, 8)
        pol = dict(activation_hint_policy(cfg, ax, shape_cfg,
                                          model_axis_size=2))
        pol['__mesh__'] = mesh
        pol['__moe_groups__'] = 8 * 2
        bsh = NamedSharding(mesh, P(("pod", "data"), None))
        with jax.set_mesh(mesh), sharding_policy(pol):
            jstep = jax.jit(step, in_shardings=(psh, None, bsh, bsh))
            p_sh, loss_sh = jstep(params, opt, toks, labels)
        print("LOSS", float(loss_ref), float(loss_sh))
        d = max(float(jnp.abs(a - b).max())
                for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)))
        print("MAXDIFF", d)
        assert abs(float(loss_ref) - float(loss_sh)) < 1e-4
        assert d < 1e-4
        print("OK")
    """)
    assert "OK" in out


def test_compressed_pod_allreduce_close_to_exact():
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.dist.compression import compressed_psum_mean, psum_mean
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("pod", "data"))
        g = jax.random.normal(jax.random.key(0), (4, 64, 128))

        def exact(x):
            return psum_mean({"g": x}, "pod")["g"]

        def comp(x):
            out, err = compressed_psum_mean({"g": x}, "pod")
            return out["g"], err["g"]

        with jax.set_mesh(mesh):
            ex = jax.jit(jax.shard_map(
                exact, mesh=mesh, in_specs=P("pod", None, None),
                out_specs=P("pod", None, None),
                axis_names={"pod"}, check_vma=False))(g)
            cm, err = jax.jit(jax.shard_map(
                comp, mesh=mesh, in_specs=P("pod", None, None),
                out_specs=(P("pod", None, None), P("pod", None, None)),
                axis_names={"pod"}, check_vma=False))(g)
        rel = float(jnp.abs(cm - ex).max() / jnp.abs(ex).max())
        print("REL", rel)
        assert rel < 0.02          # int8 quantization error bound
        # error feedback residual equals local quantization error
        assert float(jnp.abs(err).max()) < float(jnp.abs(g).max()) / 50
        print("OK")
    """)
    assert "OK" in out


def test_elastic_checkpoint_reshard():
    """Save on 1 device, restore onto an 8-device mesh with new shardings."""
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.checkpoint import Checkpointer
        from repro.launch.mesh import make_mesh
        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        d = tempfile.mkdtemp()
        ck = Checkpointer(d)
        ck.save(1, tree, blocking=True)
        mesh = make_mesh((4, 2), ("data", "model"))
        sh = {"w": NamedSharding(mesh, P("data", "model"))}
        out = ck.restore(tree, shardings=sh)
        assert out["w"].sharding.spec == P("data", "model")
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(tree["w"]))
        print("OK")
    """)
    assert "OK" in out
