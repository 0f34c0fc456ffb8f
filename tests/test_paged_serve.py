"""Continuous batching / block-paged KV pool (serve/paging.py).

The tentpole contract, property-tested:

* **Admission-order bit-identity** — N mixed-length requests admitted in
  *random interleavings* (staggered admissions, pool exhaustion, page
  reuse) produce per-request token streams bit-identical to the dense
  single-request oracle ``ServeEngine.generate``.
* **Exhaustion queues, never drops** — a pool too small for the offered
  load refuses admission (``admit() -> None``); every refused request is
  eventually served, and ``freed == allocated`` at drain.
* **Pages as the migration unit** — ``snapshot_pages``/``restore_pages``
  moves one in-flight request between engines token-identically.
* **Simulator twin** — ``Replica.slots=1`` is bit-identical to the
  original single-chain ``simulate_serving``; ``slots>1`` only helps.
* **Sharded paged decode** — a mesh-backed paged engine matches the
  unmeshed oracle (subprocess, fake multi-device).
"""

import dataclasses

import numpy as np
import pytest

import jax

from _hypothesis_compat import given, settings, st
from _subproc import run_sub as _run_sub

from repro.models.config import ModelConfig
from repro.models.model import init_params
from repro.sched_integration import (
    POLICIES,
    Replica,
    default_fleet,
    make_requests,
    pow2_bucket,
    simulate_serving,
)
from repro.serve import HeftFrontEnd, ReplicaHandle, ServeEngine

settings.register_profile("ci", max_examples=10, deadline=None)
settings.load_profile("ci")

CFG = ModelConfig(name="t", num_layers=2, d_model=32, num_heads=4,
                  num_kv_heads=4, d_ff=64, vocab_size=64,
                  param_dtype="float32", compute_dtype="float32")

# Module-level lazy singletons instead of fixtures: the hypothesis fallback
# shim (no hypothesis in the image) wraps @given tests with a zero-arg
# signature, so fixtures can't be injected into property tests.
_CACHE: dict = {}


def _params():
    if "params" not in _CACHE:
        _CACHE["params"] = init_params(jax.random.key(0), CFG)
    return _CACHE["params"]


def _oracle():
    if "oracle" not in _CACHE:
        _CACHE["oracle"] = ServeEngine(CFG, _params(), max_len=32)
    return _CACHE["oracle"]


def _requests(n, rng, smax=32, nt_max=8):
    out = []
    for _ in range(n):
        nt = int(rng.integers(1, nt_max))
        s0 = int(rng.integers(2, smax - nt))
        out.append((rng.integers(1, CFG.vocab_size, size=s0).astype(np.int32),
                    nt))
    return out


def _drain(eng, reqs, order):
    """Admit ``reqs`` in ``order`` (FIFO, queue-on-refusal) and run the
    admission/decode/retire loop until every request retires."""
    pending = list(order)
    slot_req = {}
    out = {}
    guard = 0
    while len(out) < len(reqs):
        while pending:
            slot = eng.admit(*reqs[pending[0]])
            if slot is None:
                break
            slot_req[slot] = pending.pop(0)
        eng.decode_tick()
        for slot in eng.finished_slots():
            out[slot_req.pop(slot)] = eng.retire(slot)
        guard += 1
        assert guard < 10_000, "paged drain did not converge"
    return out


# ---------------------------------------------------------------------------
# tentpole: admission-order bit-identity vs the dense oracle
# ---------------------------------------------------------------------------

@given(seed=st.integers(0, 10_000))
def test_random_interleaving_bit_identical_to_dense(seed):
    """Any admission interleaving (driven by a tiny exhaustible pool forcing
    queueing + page reuse) reproduces the dense oracle token-for-token."""
    rng = np.random.default_rng(seed)
    reqs = _requests(5, rng)
    oracle = [_oracle().generate(p[None], nt)[0] for p, nt in reqs]
    eng = ServeEngine(CFG, _params(), max_len=32)
    eng.start_paged(max_batch=int(rng.integers(2, 5)), page_size=8)
    order = rng.permutation(len(reqs)).tolist()
    out = _drain(eng, reqs, order)
    for i in range(len(reqs)):
        np.testing.assert_array_equal(out[i], oracle[i])
    pool = eng.paged.pool
    assert pool.allocated == pool.freed            # freed == allocated
    assert pool.free_pages == pool.num_pages       # fully drained


@pytest.mark.parametrize("arch,attn", [("yi_34b", "paged"),
                                       ("jamba_v0_1_52b", "paged"),
                                       ("first_dense_gqa", "paged"),
                                       ("gemma2_9b", "gather")])
def test_paged_decode_bit_identical_across_cache_structures(arch, attn):
    """GQA with 7 query heads per KV head, Mamba state beside attention,
    and a leading unscanned layer (all attend in the pool), and local
    windows with a soft-cap (a gathered view): every request matches the
    dense oracle."""
    from repro.configs import get_smoke_config

    cfg = (CFG.with_(num_layers=3, num_kv_heads=2, first_dense_layers=1)
           if arch == "first_dense_gqa" else get_smoke_config(arch))
    params = init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    # Prompt lengths are whole Mamba chunks (jamba's prefill needs them).
    reqs = [(rng.integers(1, cfg.vocab_size, size=s).astype(np.int32), nt)
            for s, nt in [(8, 4), (12, 6), (4, 3)]]
    oracle = ServeEngine(cfg, params, max_len=32)
    want = [oracle.generate(p[None], nt)[0] for p, nt in reqs]
    eng = ServeEngine(cfg, params, max_len=32)
    eng.start_paged(max_batch=2, page_size=8)
    assert eng.paged.attn == attn
    out = _drain(eng, reqs, [0, 1, 2])
    for i in range(len(reqs)):
        np.testing.assert_array_equal(out[i], want[i])


def test_exhaustion_queues_never_drops():
    """A pool with room for ONE sequence still serves everything (strictly
    serialized), token-identically; admit() refuses instead of dropping."""
    rng = np.random.default_rng(3)
    reqs = _requests(4, rng)
    eng = ServeEngine(CFG, _params(), max_len=32)
    eng.start_paged(max_batch=4, page_size=8, num_pages=4)   # 4 pages = 1 seq
    refused = 0
    pending = list(range(len(reqs)))
    slot_req, out = {}, {}
    while len(out) < len(reqs):
        while pending:
            slot = eng.admit(*reqs[pending[0]])
            if slot is None:
                refused += 1
                break
            slot_req[slot] = pending.pop(0)
        eng.decode_tick()
        for slot in eng.finished_slots():
            out[slot_req.pop(slot)] = eng.retire(slot)
    assert refused > 0                             # exhaustion actually hit
    for i, (p, nt) in enumerate(reqs):
        np.testing.assert_array_equal(out[i],
                                      _oracle().generate(p[None], nt)[0])
    assert eng.paged.pool.allocated == eng.paged.pool.freed


def test_admit_rejects_impossible_and_validates():
    eng = ServeEngine(CFG, _params(), max_len=32)
    eng.start_paged(max_batch=2, page_size=8)
    with pytest.raises(ValueError):                # S0+nt > max_len
        eng.admit(np.ones(30, dtype=np.int32), 8)
    with pytest.raises(ValueError):                # new_tokens < 1
        eng.admit(np.ones(4, dtype=np.int32), 0)
    with pytest.raises(ValueError):                # page_size ∤ max_len
        ServeEngine(CFG, _params(), max_len=32).start_paged(page_size=7)


def test_free_pages_accounting():
    eng = ServeEngine(CFG, _params(), max_len=32)
    eng.start_paged(max_batch=2, page_size=8)      # 8 pages total
    assert eng.free_pages() == 8
    slot = eng.admit(np.arange(1, 10, dtype=np.int32), 4)   # 13 tok → 2 pages
    assert eng.free_pages() == 6
    while not eng.finished_slots():
        eng.decode_tick()
    eng.retire(slot)
    assert eng.free_pages() == 8
    assert eng.paged.pool.allocated == eng.paged.pool.freed == 2


# ---------------------------------------------------------------------------
# pages as the migration / recovery unit
# ---------------------------------------------------------------------------

def test_snapshot_restore_moves_request_between_engines():
    """Kill-and-recover at page granularity: mid-decode snapshot on engine A
    restores on engine B and finishes token-identically."""
    rng = np.random.default_rng(7)
    (p, nt), = _requests(1, rng, nt_max=8)
    nt = max(nt, 4)                                # leave ticks to split
    oracle = _oracle().generate(p[None], nt)[0]
    a = ServeEngine(CFG, _params(), max_len=32)
    a.start_paged(max_batch=2, page_size=8)
    slot = a.admit(p, nt)
    a.decode_tick()                                # a couple of committed steps
    snap = a.snapshot_pages(slot)
    b = ServeEngine(CFG, _params(), max_len=32)
    b.start_paged(max_batch=2, page_size=8)
    slot_b = b.restore_pages(snap)
    assert slot_b is not None
    while not b.finished_slots():
        b.decode_tick()
    np.testing.assert_array_equal(b.retire(slot_b), oracle)


# ---------------------------------------------------------------------------
# front end: run_continuous drains its HEFT_RT-mapped queue
# ---------------------------------------------------------------------------

def test_run_continuous_matches_oracle_and_balances():
    rng = np.random.default_rng(11)
    reqs = _requests(6, rng)
    fleet = [ReplicaHandle(f"replica{i}",
                           ServeEngine(CFG, _params(), max_len=32), speed=s)
             for i, s in enumerate([1.0, 0.7])]
    front = HeftFrontEnd(fleet)
    outs, stats = front.run_continuous(
        reqs, arrival_ticks=[0, 0, 1, 2, 2, 5],
        max_batch=2, page_size=8, num_pages=8)
    for i, (p, nt) in enumerate(reqs):
        np.testing.assert_array_equal(outs[i],
                                      _oracle().generate(p[None], nt)[0])
    assert stats["allocated"] == stats["freed"]
    assert sum(stats["processed"].values()) == len(reqs)


# ---------------------------------------------------------------------------
# simulator twin: Replica.slots
# ---------------------------------------------------------------------------

def test_slots1_bit_identical_and_slots_help():
    load = lambda: make_requests(30.0, 6.0, seed=0)     # noqa: E731
    base = simulate_serving(default_fleet(), load(), POLICIES["heft_rt"](),
                            active_params=7e9)
    again = simulate_serving([dataclasses.replace(r, slots=1)
                              for r in default_fleet()], load(),
                             POLICIES["heft_rt"](), active_params=7e9)
    np.testing.assert_array_equal(base.finish_times, again.finish_times)
    np.testing.assert_array_equal(base.final_avail, again.final_avail)
    assert base.p99_latency == again.p99_latency
    multi = simulate_serving([dataclasses.replace(r, slots=4)
                              for r in default_fleet()], load(),
                             POLICIES["heft_rt"](), active_params=7e9)
    assert multi.p99_latency <= base.p99_latency + 1e-12


def test_multislot_straggler_remap_guard():
    """The controller's straggler remap can't re-attribute chain suffixes;
    it must fail loudly on multi-slot replicas, not corrupt horizons."""
    from repro.sched_integration import FleetController, FleetControllerConfig

    fleet = [dataclasses.replace(r, slots=2) for r in default_fleet()]
    from repro.sched_integration import grown_replica_factory

    ctl = FleetController(
        FleetControllerConfig(straggler_factor=1.01,
                              straggler_min_backlog_s=0.0),
        grown_replica_factory("g", (2, 2)))
    with pytest.raises(ValueError, match="multi-slot"):
        simulate_serving(fleet, make_requests(400.0, 4.0, seed=0),
                         POLICIES["heft_rt"](), active_params=7e9,
                         controller=ctl)


def test_pow2_bucket():
    assert [pow2_bucket(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    assert pow2_bucket(1, min_bucket=8) == 8


# ---------------------------------------------------------------------------
# mesh-backed paged decode (subprocess: fake multi-device)
# ---------------------------------------------------------------------------

def test_sharded_paged_decode_matches_oracle():
    _run_sub("""
import numpy as np, jax
from repro.dist.sharding import MeshAxes
from repro.launch.mesh import make_debug_mesh
from repro.models.config import ModelConfig
from repro.models.model import init_params
from repro.serve import ServeEngine

cfg = ModelConfig(name='t', num_layers=2, d_model=32, num_heads=4,
                  num_kv_heads=4, d_ff=64, vocab_size=64,
                  param_dtype='float32', compute_dtype='float32')
params = init_params(jax.random.key(0), cfg)
oracle = ServeEngine(cfg, params, max_len=32)
rng = np.random.default_rng(0)
reqs = [(rng.integers(1, 64, size=s).astype(np.int32), nt)
        for s, nt in [(5, 4), (9, 6), (7, 3)]]
want = [oracle.generate(p[None], nt)[0] for p, nt in reqs]

mesh = make_debug_mesh((2, 2), ("data", "model"))
eng = ServeEngine(cfg, params, max_len=32, mesh=mesh, axes=MeshAxes())
eng.start_paged(max_batch=2, page_size=8)
pending = list(range(3)); slots = {}; out = {}
while len(out) < 3:
    while pending:
        s = eng.admit(*reqs[pending[0]])
        if s is None: break
        slots[s] = pending.pop(0)
    eng.decode_tick()
    for s in eng.finished_slots():
        out[slots.pop(s)] = eng.retire(s)
for i in range(3):
    np.testing.assert_array_equal(out[i], want[i])
print('SHARDED_PAGED_OK')
""", devices=8)


# ---------------------------------------------------------------------------
# the serve launcher's paged path (what chip_smoke.py drives on the chip)
# ---------------------------------------------------------------------------

def test_launcher_paged_fused_serves_every_request():
    from repro.launch import serve

    res = serve.main(["--paged", "--fused-scheduler", "--replicas", "2",
                      "--requests", "4", "--new-tokens", "5",
                      "--prompt-lens", "8,12", "--max-len", "32"])
    stats = res["stats"]
    assert stats["fused_decisions"] + stats["host_decisions"] == 4
    assert stats["fused_decisions"] > 0
    assert stats["allocated"] == stats["freed"]
    assert [o.shape for o in res["outputs"]] == [(1, 13), (1, 17)] * 2
    assert sum(stats["processed"].values()) == 4


def test_launcher_full_config_cuts_only_depth():
    from repro.launch import serve

    args = serve.build_parser().parse_args(["--full", "--num-layers", "16"])
    cfg = serve.served_config(args)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size, cfg.param_dtype) == (
        16, 4096, 32, 32, 128, 11008, 102400, "bfloat16")
    with pytest.raises(SystemExit):
        serve.served_config(serve.build_parser().parse_args(
            ["--num-layers", "2"]))
    with pytest.raises(SystemExit):
        serve.served_config(serve.build_parser().parse_args(
            ["--full", "--num-layers", "31"]))
    # gemma2 alternates local and global layers: depth is cut in pairs.
    gemma = ["--arch", "gemma2-9b", "--full", "--num-layers"]
    assert serve.served_config(
        serve.build_parser().parse_args(gemma + ["4"])).num_layers == 4
    with pytest.raises(SystemExit):
        serve.served_config(serve.build_parser().parse_args(gemma + ["3"]))


def _serve_paged(cfg, reqs, max_len=32):
    """Serve ``reqs`` on a paged engine of ``cfg`` over the shared weights,
    cast to the dtypes ``cfg`` gives them."""
    from repro.models.model import param_specs

    params = jax.tree.map(lambda x, s: x.astype(s.dtype), _params(),
                          param_specs(cfg))
    eng = ServeEngine(cfg, params, max_len=max_len)
    eng.start_paged(max_batch=4, page_size=8)
    out = _drain(eng, reqs, list(range(len(reqs))))
    return eng, [out[i] for i in range(len(reqs))]


def test_reference_check_names_the_first_wrong_token():
    from repro.launch.serve import check_against_reference

    reqs = _requests(4, np.random.default_rng(3))
    eng, outs = _serve_paged(CFG, reqs)
    for (prompt, _), seq in zip(reqs, outs):
        worst, msg = check_against_reference(CFG, eng, prompt, seq)
        assert msg is None and worst <= 1.0, (worst, msg)
    prompt, nt = reqs[0]
    bad = outs[0].copy()
    k = len(prompt) + nt - 1
    bad[k] = (bad[k] + 1) % CFG.vocab_size
    worst, msg = check_against_reference(CFG, eng, prompt, bad)
    assert msg is not None and msg.startswith(f"step {nt - 1} (position {k})")


def test_reference_check_fails_a_path_below_the_stated_precision():
    """The config states float32; a paged path that computes in bfloat16
    picks near-tied tokens the float32 reference ranks lower by many
    float32 ulps, and the check must say so."""
    from repro.launch.serve import check_against_reference

    reqs = _requests(8, np.random.default_rng(4), nt_max=16)
    low = dataclasses.replace(CFG, param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    eng, outs = _serve_paged(low, reqs)
    results = [check_against_reference(CFG, eng, p, seq)
               for (p, _), seq in zip(reqs, outs)]
    assert any(msg is not None for _, msg in results), results


def test_engine_initialises_weights_from_a_key():
    """A key in place of weights: ``init_params_on``'s values, made by one
    compiled program in the engine's placement.  They match the eager
    ``init_params`` to float32 rounding (the compiled init may fuse the
    scale into the draw)."""
    from repro.models.model import init_params_on

    eng = ServeEngine(CFG, jax.random.key(0), max_len=32)
    placed = init_params_on(jax.random.key(0), CFG)
    for a, b, c in zip(jax.tree.leaves(eng.params), jax.tree.leaves(placed),
                       jax.tree.leaves(_params())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-6, atol=0)
