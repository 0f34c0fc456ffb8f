"""Compile-only checks against a described TPU v5e (no chip attached).

The TPU compiler is installed with JAX, so the scheduler kernels and the
paged decode tick are compiled here for a v5e that is described, not
present: what Mosaic or XLA:TPU would refuse (unaligned slices, too much
fast memory, a program that does not fit in HBM) fails here at no chip
time.  Nothing runs, so these say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file.  Where no topology can be described, the tests skip.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decision_hw, heft_rt_hw
from repro.models.model import param_specs
from repro.obs.device import zero_counters
from repro.serve.paging import paged_programs, pool_shapes

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off (its entries for a device that is not attached cannot be
    read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _on(chip, tree):
    return jax.tree.map(lambda s: _sds(chip, s.shape, s.dtype), tree)


# Ready-queue depth x PE buckets the fabric dispatches: its smallest
# (min_bucket=8, min_pe_bucket=4) and one a larger fleet reaches.
@pytest.mark.parametrize("depth,pes", [(8, 4), (64, 8)])
@pytest.mark.parametrize("kernel", ["decision_hw", "heft_rt_hw"])
def test_scheduler_kernel_compiles_for_v5e(chip, kernel, depth, pes):
    f32 = jnp.float32
    avg = _sds(chip, (depth,), f32)
    ex = _sds(chip, (depth, pes), f32)
    avail = _sds(chip, (pes,), f32)
    if kernel == "decision_hw":
        mask = _sds(chip, (pes,), jnp.bool_)
        lowered = jax.jit(lambda a, e, v, m: decision_hw(
            a, e, v, m, interpret=False)).lower(avg, ex, avail, mask)
    else:
        lowered = jax.jit(lambda a, e, v: heft_rt_hw(
            a, e, v, interpret=False)).lower(avg, ex, avail)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("program",
                         ["tick", "tick_sched", "tick_sched_counted"])
def test_paged_tick_layer_compiles_for_v5e(chip, program):
    """One deepseek-7b layer at published widths (bf16, d_model 4096, 32
    heads of 128, d_ff 11008, vocab 102400) through the paged tick: 4 lanes
    of 1024 tokens in 16-token pages, as the one-chip smoke run serves."""
    cfg = get_config("deepseek-7b").with_(num_layers=1)
    lanes, max_len, page = 4, 1024, 16
    pp = max_len // page
    params = _on(chip, param_specs(cfg))
    pools = _on(chip, pool_shapes(cfg, lanes * pp, page, lanes, max_len))
    i32 = jnp.int32
    args = [params, pools, _sds(chip, (lanes, pp), i32),
            _sds(chip, (lanes,), i32), _sds(chip, (lanes,), i32),
            _sds(chip, (lanes, 1), i32)]
    donate = (1,)
    if program != "tick":
        depth, pes = 8, 4
        args += [_sds(chip, (depth,), jnp.float32),
                 _sds(chip, (depth, pes), jnp.float32),
                 _sds(chip, (depth,), jnp.bool_),
                 _sds(chip, (pes,), jnp.float32),
                 _sds(chip, (pes,), jnp.bool_)]
        donate = (1, 9)
    if program == "tick_sched_counted":
        ctr = zero_counters()
        args += [_sds(chip, ctr.shape, ctr.dtype), _sds(chip, (pes,), jnp.bool_)]
        donate = (1, 9, 11)
    fn = paged_programs(cfg, page, pp)[program]
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    mem = compiled.memory_analysis()
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= weights
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_HBM_BYTES)


@pytest.mark.parametrize("program", ["tick", "tick_sched_counted"])
def test_paged_tick_reads_the_pool_in_place_on_v5e(chip, program,
                                                   monkeypatch):
    """Two deepseek-7b layers at published widths through the paged tick
    with the TPU kernel path (what ``use_kernel`` picks on a chip): 4 lanes
    of 1024 tokens.  The tick holds no dense (L, B, Smax, KV, hd) view: its
    temporaries stay below one such view of K alone, where a gathered tick
    needs two of K and V each."""
    from repro.kernels import paged_attention

    monkeypatch.setattr(paged_attention, "use_kernel", lambda: True)
    cfg = get_config("deepseek-7b").with_(num_layers=2)
    lanes, max_len, page = 4, 1024, 16
    pp = max_len // page
    params = _on(chip, param_specs(cfg))
    pools = _on(chip, pool_shapes(cfg, lanes * pp, page, lanes, max_len))
    i32 = jnp.int32
    args = [params, pools, _sds(chip, (lanes, pp), i32),
            _sds(chip, (lanes,), i32), _sds(chip, (lanes,), i32),
            _sds(chip, (lanes, 1), i32)]
    donate = (1,)
    if program == "tick_sched_counted":
        depth, pes = 8, 4
        ctr = zero_counters()
        args += [_sds(chip, (depth,), jnp.float32),
                 _sds(chip, (depth, pes), jnp.float32),
                 _sds(chip, (depth,), jnp.bool_),
                 _sds(chip, (pes,), jnp.float32),
                 _sds(chip, (pes,), jnp.bool_),
                 _sds(chip, ctr.shape, ctr.dtype),
                 _sds(chip, (pes,), jnp.bool_)]
        donate = (1, 9, 11)
    fn = paged_programs(cfg, page, pp)[program]
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    dense_k = (cfg.num_layers * lanes * max_len * cfg.num_kv_heads
               * cfg.head_dim * 2)
    assert compiled.memory_analysis().temp_size_in_bytes < dense_k
