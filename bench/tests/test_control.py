"""The control comes out not correct: the configuration's plain reference,
computed one precision lower (fp8 under bf16), put in the program's place,
fails the ``logit_gap`` limit on the same prompts and served tokens where
the program passes it.  At a smoke size on the CPU; the readings at the
cells' own sizes come from ``bench/tools/limits.py`` on the chip."""

import numpy as np
import pytest

from bench.lib import check, heft, spec, system, traffic
from bench.tests import smoke
from bench.tests.test_faults import LIMIT, bf16_cell


@pytest.fixture(scope="module")
def served():
    from bench.lib.cell import devices_for

    cell = bf16_cell()
    devices_for(1, require_tpu=False)
    sys_ = system.build(cell.config, spec.reference_module(cell.config),
                        seed=1, chips=1)
    system.warm(sys_, traffic.grid_lengths(cell.traffic))
    return cell, sys_


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_the_program_passes(served, seed):
    cell, sys_ = served
    system.set_weights(sys_, seed)
    sched = traffic.schedule(cell.traffic, rate_per_s=40.0, lead_in_s=0.2,
                             seconds=0.8, seed=seed, vocab_size=sys_.hp["v"])
    run = system.serve(sys_, sched, lead_in_s=0.2, seconds=0.8)
    sample = check.sample(run.window_reqs, seed, 60)
    assert sum(r.new_tokens for r in sample) >= 60
    program = check.logit_gap(sys_.ref, sys_.params, sys_.hp, sample)
    control = check.logit_gap(sys_.ref, sys_.params, sys_.hp, sample,
                              control=True)
    assert program <= LIMIT < control, (program, control)


def test_control_precision_is_the_next_one_down():
    ref = spec.reference_module(smoke.CONFIG)
    assert ref.control_precision({"dtype": "bfloat16"}) == "fp8_e4m3"
    assert ref.control_precision({"dtype": "float32"}) == "bf16"


def test_fp8_rounding_keeps_three_mantissa_bits():
    import jax.numpy as jnp

    ref = spec.reference_module(smoke.CONFIG)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 512)),
                    jnp.float32)
    q = np.asarray(ref.fp8_e4m3(x))
    rel = np.abs(q - np.asarray(x)) / np.abs(np.asarray(x))
    big = np.abs(np.asarray(x)) > 0.05 * np.abs(np.asarray(x)).max()
    assert 0 < rel[big].max() <= 2.0 ** -4


def test_heft_reference_agrees_with_the_program_oracle():
    from repro.core import heft_rt_numpy

    rng = np.random.default_rng(0)
    for n, p in [(1, 2), (5, 2), (9, 3), (16, 4)]:
        # Dyadic times: float32 and the oracle's float64 agree exactly.
        avg = rng.choice([2.0**-6, 2.0**-5, 2.0**-4], n)
        ex = np.repeat(avg[:, None], p, axis=1)     # equal replicas: ties
        avail = rng.choice([0.0, 0.125], p).astype(np.float32)
        got = heft.heft_rt(avg, ex, avail)
        want = heft_rt_numpy(avg.astype(np.float32),
                             ex.astype(np.float32), avail)
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(got[1], want[1])
        assert np.array_equal(got[4], want[4])


def test_misrouting_is_counted():
    from types import SimpleNamespace as NS

    reqs = [NS(rid=i, due=float(i), replica=i % 2) for i in range(4)]
    dec = ("tick", np.ones(2), np.ones((2, 2)),
           (np.array([1, 0]), np.array([1, 0])))
    dec2 = ("tick", np.ones(2), np.ones((2, 2)),
            (np.array([0, 1]), np.array([0, 0])))
    assert check.misrouted([dec, dec2], reqs) == 1
    assert check.misrouted([dec], reqs) == 2      # two never mapped
