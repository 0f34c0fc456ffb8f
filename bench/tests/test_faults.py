"""The correctness check catches a broken timed path: each fault a serving
cell can have is planted under a whole run of the harness (at a smoke
size on the CPU), and the run must come out not correct."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import spec
from bench.tests import smoke

# bf16 like the benchmark's configurations; the limit sits between the
# program's readings at this size (at most 0.031 over 8 seeds) and the
# fp8 control's (at least 0.214).
LIMIT = 0.08


def bf16_cell(**kw):
    """The smoke cell in bf16, loaded so that ticks run several lanes,
    with every window request in the sample."""
    return smoke.cell(torch_dtype="bfloat16",
                      params={"limits": {"logit_gap": LIMIT},
                              "rate_per_s": 80.0, "sample_tokens": 10_000},
                      **kw)


def test_sound_run_is_correct():
    line = smoke.run_line(bf16_cell())
    assert line["correct"] is True, line["checks"]


def _stale_state(monkeypatch):
    """The decode tick hands back the page pool it was given: the tick's
    state never changes."""
    import repro.serve.paging as paging

    orig = paging.paged_programs

    def keep_pools(fn):
        def call(params, pools, *rest):
            out = fn(params, pools, *rest)
            return (out[0], pools) + tuple(out[2:])
        return call

    def programs(*a, **kw):
        fns = orig(*a, **kw)
        for name in ("tick", "tick_sched", "tick_sched_counted"):
            fns[name] = keep_pools(fns[name])
        return fns

    monkeypatch.setattr(paging, "paged_programs", programs)


def _half_batch(monkeypatch):
    """Half of the lanes computed; the other half given their results."""
    import repro.models.model as model

    orig = model.decode_step

    def half(p, c, t, pos, cfg):
        logits, new = orig(p, c, t, pos, cfg)
        b = logits.shape[0]
        if b >= 2:
            logits = jnp.concatenate([logits[:b // 2]] * 2)
        return logits, new

    monkeypatch.setattr(model, "decode_step", half)


def _token_altered(monkeypatch):
    """A token changed where the tick produces it (one every 25 ticks)."""
    from repro.serve.paging import PagedRuntime

    orig = PagedRuntime.decode_tick
    calls = {"n": 0}

    def tick(self, sched=None):
        res = orig(self, sched)
        out = res if sched is None else res[0]
        if out:
            calls["n"] += 1
            if calls["n"] % 25 == 0:
                s = min(out)
                t = (out[s] + 1) % self.engine.cfg.vocab_size
                out[s] = self.slots[s].tokens[-1] = t
        return res

    monkeypatch.setattr(PagedRuntime, "decode_tick", tick)


def _decision_altered(monkeypatch):
    """A scheduling decision sends a request to the other replica (one in
    every seven fused decisions)."""
    from repro.sched_integration.fabric import MappingFabric

    orig = MappingFabric.commit_tick_decision
    calls = {"n": 0}

    def commit(self, n, buf, new_avail, counters=None):
        order, assignment, start, finish, avail = orig(self, n, buf,
                                                       new_avail, counters)
        calls["n"] += 1
        if calls["n"] % 7 == 0:
            assignment = np.array(assignment, copy=True)
            assignment[0] = 1 - assignment[0]
        return order, assignment, start, finish, avail

    monkeypatch.setattr(MappingFabric, "commit_tick_decision", commit)


@pytest.mark.parametrize("fault,number", [
    (_stale_state, "logit_gap"),
    (_half_batch, "logit_gap"),
    (_token_altered, "logit_gap"),
    (_decision_altered, "heft_mismatch"),
])
def test_fault_is_not_correct(monkeypatch, fault, number):
    fault(monkeypatch)
    line = smoke.run_line(bf16_cell(), seconds=1.0)
    assert line["correct"] is False
    c = line["checks"][number]
    assert c["value"] > c["limit"], line["checks"]


# The sharded cell's fault: the exchange between chips left out.  On a
# 1x4 slice the FFN's down projection is row-parallel, each chip holding a
# quarter of d_ff, and an all-reduce sums the four partial products; the
# planted fault keeps one chip's partial sum.  Run on four virtual CPU
# devices in a fresh process (the device count is fixed at start-up).
SHARDED = r'''
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench.tests import smoke
from bench.tests.test_faults import LIMIT
if {fault}:
    import repro.models.ffn as ffn
    def local_only(params, x, cfg):
        import jax
        f = params["w_down"].shape[0] // 4
        h = jax.nn.silu(x @ params["w_gate"][:, :f]) * (x @ params["w_up"][:, :f])
        return h @ params["w_down"][:f]
    ffn.ffn_block = local_only
    import repro.models.transformer as tr
    tr.ffn_block = local_only
cell = smoke.cell(torch_dtype="bfloat16",
                  params={{"limits": {{"logit_gap": LIMIT}}}},
                  serving={{"replicas": 1, "mesh": "1x4", "max_batch": 4}})
cell.chips = 4
print(json.dumps(smoke.run_line(cell, seconds=1.0)))
'''


@pytest.mark.parametrize("fault", [False, True])
def test_sharded_exchange_left_out(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SHARDED.format(root=str(spec.ROOT), src=str(spec.ROOT / "src"),
                          fault=fault)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is (not fault), line["checks"]
