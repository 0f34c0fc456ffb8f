"""The operation and byte counts against the model's own parameter shapes,
and the peak table."""

import jax
import numpy as np
import pytest

from bench.lib import roofline, spec
from bench.tests import smoke


def _hp(config):
    return spec.reference_module(config).sizes(config)


@pytest.mark.parametrize("config_name", ["smoke", "deepseek-7b-l16x2"])
def test_weight_counts_match_program_shapes(config_name):
    from bench.lib.system import model_config
    from repro.models.model import param_specs

    config = (smoke.CONFIG if config_name == "smoke" else spec._load_json(
        spec.BENCH / "configs" / f"{config_name}.json"))
    hp = _hp(config)
    shapes = param_specs(model_config(config))
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]

    def name(path):
        return str(getattr(path[-1], "key", path[-1]))

    matrices = sum(int(np.prod(s.shape)) for p, s in leaves
                   if name(p).startswith("w") or name(p) == "lm_head")
    assert roofline.matrix_params(hp) == matrices

    # Bytes of one lane at position 0: every leaf but the embedding table,
    # one embedding row, and one token's key and value written per layer.
    _, nbytes = roofline.tick_work(hp, [0])
    leaf_bytes = sum(s.size * s.dtype.itemsize for p, s in leaves
                     if name(p) != "embed")
    wb = np.dtype(config["torch_dtype"].replace("bfloat16", "float16")
                  ).itemsize
    kv_write = 2 * hp["layers"] * hp["kv"] * hp["hd"] * wb
    assert nbytes == leaf_bytes + hp["d"] * wb + kv_write


def test_only_live_lanes_and_their_positions_count():
    hp = _hp(smoke.CONFIG)
    base_f, base_b = roofline.tick_work(hp, [])
    f3, b3 = roofline.tick_work(hp, [10, 20, 30])
    L, kv, hd, h = hp["layers"], hp["kv"], hp["hd"], hp["h"]
    wb = 4
    # Three lanes: three embedding rows, and the keys and values each lane
    # has cached plus its new token -- not a bucket of four lanes, and not
    # the max_len-long gathered view of each slot's pages.
    assert b3 - base_b == 3 * hp["d"] * wb + wb * 2 * L * kv * hd * (60 + 3)
    assert f3 - base_f == (2 * 3 * roofline.matrix_params(hp)
                           + 4 * L * h * hd * (11 + 21 + 31))
    assert roofline.tick_work(hp, [10, 20, 30]) == \
        roofline.tick_work(hp, [30, 10, 20])


def test_least_time_picks_the_binding_bound():
    peak = spec.peaks("TPU v5 lite")
    t, bound = roofline.least_time_s(197e12, 1.0, peak, 1)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = roofline.least_time_s(1.0, 4 * 819e9, peak, 4)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_decode_tick_at_real_size_is_memory_bound():
    config = spec._load_json(spec.BENCH / "configs" /
                             "deepseek-7b-l16x2.json")
    hp = _hp(config)
    flops, nbytes = roofline.tick_work(hp, [500, 600, 700, 800])
    t, bound = roofline.least_time_s(flops, nbytes, spec.peaks(
        "TPU v5 lite"), 1)
    assert bound == "memory"
    # 8.154e9 of weights less the 0.839e9 embedding table, plus the KV
    assert nbytes == pytest.approx(7.315e9 + 0.683e9, rel=1e-3)
    assert t == pytest.approx(nbytes / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("TPU v4")
