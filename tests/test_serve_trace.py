"""Spans inside the serving loop (``HeftFrontEnd.run_continuous`` and
``PagedRuntime.decode_tick`` with a ``repro.obs.Tracer`` attached).

* an idle stretch is one ``loop.idle`` span, and an empty tick records
  nothing;
* one ``request`` span per request, its offsets in order, its ``ev``
  shared with the ``sched.*`` spans of the event that decided it;
* ``tick.stage`` / ``tick.wait`` / ``tick.commit`` nest in their tick;
* ``engine.admit`` says whether it admitted; page counts add up;
* ``engine.decode_tick`` says whether attention read the page pool in
  place (``attn``): every tick of an MHA model does, no tick of a windowed
  or MLA model;
* ``host.gc`` spans come from a hook that is removed afterwards;
* tracing changes no token;
* under ``jax.profiler`` the phases land on the host plane, and the
  harness's ``engine.decode_tick`` annotations are not doubled;
* the traced benchmark run reads every span metric at a smoke size.
"""

import gc
import math

import numpy as np
import pytest

import jax

from repro.models.config import ModelConfig
from repro.models.model import init_params
from repro.obs import Tracer
from repro.sched_integration.fabric import MappingFabric
from repro.serve import HeftFrontEnd, ReplicaHandle, ServeEngine

CFG = ModelConfig(name="t", num_layers=2, d_model=32, num_heads=4,
                  num_kv_heads=4, d_ff=64, vocab_size=64,
                  param_dtype="float32", compute_dtype="float32")
SCHED = ("sched.stage", "sched.decide", "sched.adopt")
PHASES = ("tick.stage", "tick.wait", "tick.commit")
NEW_METRICS = ("sched.decide_wait_p50_ms", "sched.event_host_p50_ms",
               "sched.slot_wait_p50_ms", "admit.prefill_span_p50_ms",
               "tick.host_p50_ms", "cache.page_use", "loop.busy_share",
               "host.gc_ms_per_s")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def requests():
    rng = np.random.default_rng(7)
    out = []
    for _ in range(6):
        nt = int(rng.integers(2, 8))
        s0 = int(rng.integers(2, 32 - nt))
        out.append((rng.integers(1, CFG.vocab_size, s0).astype(np.int32), nt))
    return out


# Requests 4 and 5 arrive long after the first four are done: the loop
# spins through an idle stretch between.
ARRIVALS = [0, 0, 1, 2, 40, 41]


def _serve(params, requests, tracer, *, fused=True, num_pages=8, wrap=None):
    fleet = [ReplicaHandle(f"replica{i}",
                           ServeEngine(CFG, params, max_len=32,
                                       tracer=tracer), speed=s)
             for i, s in enumerate([1.0, 0.7])]
    if wrap is not None:
        wrap(fleet[0].engine)
    fabric = MappingFabric(2, backend="fused") if fused else None
    front = HeftFrontEnd(fleet, fabric=fabric, tracer=tracer)
    outs, _ = front.run_continuous(requests, arrival_ticks=ARRIVALS,
                                   max_batch=2, page_size=8,
                                   num_pages=num_pages, fused=fused)
    return outs


def _spans(tracer, name):
    return [e for e in tracer.events() if e.name == name and e.ph == "X"]


@pytest.fixture(scope="module")
def traced(params, requests):
    """One traced fused run, shared by the read-only checks."""
    tr = Tracer()
    outs = _serve(params, requests, tr)
    return tr, outs


def test_idle_stretch_is_one_loop_idle_span_and_no_empty_tick(traced):
    tr, _ = traced
    idle = _spans(tr, "loop.idle")
    assert len(idle) == 1
    # At most one busy tick a loop round before the stretch, and request 4
    # becomes visible at round 40.
    assert 20 <= idle[0].args["iterations"] < 40
    ticks = _spans(tr, "engine.decode_tick")
    assert ticks and all(t.args["active"] >= 1 for t in ticks)


@pytest.mark.parametrize("fused", [True, False])
def test_one_request_span_each_decided_by_a_sched_event(params, requests,
                                                        fused):
    tr = Tracer()
    _serve(params, requests, tr, fused=fused)
    reqs = _spans(tr, "request")
    assert sorted(r.args["rid"] for r in reqs) == list(range(len(requests)))
    events = {}
    for e in tr.events():
        if e.name in SCHED:
            events.setdefault(e.args["ev"], set()).add((e.name,
                                                        e.args["path"]))
    for r in reqs:
        a = r.args
        assert 0 <= a["decided_s"] <= a["admitted_s"] <= a["first_token_s"]
        assert a["first_token_s"] * 1e6 <= r.dur
        assert a["tokens"] == requests[a["rid"]][1]
        assert a["replica"] in (0, 1)
        assert (("sched.decide", a["path"]) in events[a["ev"]]
                or ("sched.adopt", a["path"]) in events[a["ev"]])
    paths = {r.args["path"] for r in reqs}
    # The fused loop decides in the tick when a lane is live, on the host
    # when the fleet is idle; the plain loop always on the host.
    assert paths == ({"fused", "host"} if fused else {"host"})
    if fused:
        fused_evs = {ev for ev, s in events.items()
                     if ("sched.adopt", "fused") in s}
        assert fused_evs and all(("sched.decide", "fused") not in events[e]
                                 for e in fused_evs)


def test_tick_phases_nest_inside_their_decode_tick(traced):
    tr, _ = traced
    ticks = _spans(tr, "engine.decode_tick")
    phases = [e for e in tr.events() if e.name in PHASES]
    assert len(phases) == 3 * len(ticks)
    for t in ticks:
        inside = [p for p in phases
                  if t.ts <= p.ts and p.ts + p.dur <= t.ts + t.dur]
        assert [p.name for p in sorted(inside, key=lambda p: p.ts)] == \
            list(PHASES)


def test_pages_written_never_exceed_pages_reserved(traced):
    tr, _ = traced
    ticks = _spans(tr, "engine.decode_tick")
    for t in ticks:
        a = t.args
        assert 1 <= a["pages_written"] <= a["pages_reserved"]
        assert a["pages_reserved"] <= 8        # the pool's pages


def test_every_tick_of_an_mha_model_attends_in_the_pool(traced):
    tr, _ = traced
    ticks = _spans(tr, "engine.decode_tick")
    assert ticks and all(t.args["attn"] == "paged" for t in ticks)


@pytest.mark.parametrize("arch", ["gemma2_9b", "deepseek_v2_236b"])
def test_window_and_mla_models_gather_a_dense_view(arch):
    """gemma2 (local windows, attention soft-cap) and deepseek-v2 (MLA)
    keep the gathered view on every tick."""
    from repro.configs import get_smoke_config

    cfg = get_smoke_config(arch)
    tr = Tracer()
    eng = ServeEngine(cfg, init_params(jax.random.key(0), cfg), max_len=16,
                      tracer=tr)
    eng.start_paged(max_batch=2, page_size=8)
    eng.admit(np.arange(1, 9, dtype=np.int32), 3)
    while not eng.finished_slots():
        eng.decode_tick()
    ticks = _spans(tr, "engine.decode_tick")
    assert len(ticks) == 2 and all(t.args["attn"] == "gather" for t in ticks)


def test_admit_span_reports_a_refused_call(params):
    tr = Tracer()
    eng = ServeEngine(CFG, params, max_len=32, tracer=tr)
    eng.start_paged(max_batch=2, page_size=8, num_pages=4)
    prompt = np.arange(1, 25, dtype=np.int32)
    assert eng.admit(prompt, 8) is not None     # all four pages
    assert eng.admit(prompt, 8) is None         # the pool is full
    assert [e.args["admitted"] for e in _spans(tr, "engine.admit")] == [1, 0]


def test_forced_collection_gives_a_host_gc_span(params, requests):
    before = list(gc.callbacks)
    tr = Tracer()

    def wrap(eng):
        orig, done = eng.decode_tick, []

        def decode_tick(sched=None):
            if not done:
                done.append(gc.collect())
            return orig(sched)

        eng.decode_tick = decode_tick

    _serve(params, requests, tr, wrap=wrap)
    assert gc.callbacks == before
    full = [e for e in _spans(tr, "host.gc") if e.args["generation"] == 2]
    assert full and all(e.args["collected"] >= 0 and e.dur >= 0
                        for e in full)


def test_tracing_changes_no_token(params, requests, traced):
    _, traced_outs = traced
    plain = _serve(params, requests, None)
    for a, b in zip(plain, traced_outs):
        np.testing.assert_array_equal(a, b)


def test_phases_reach_the_profiler_host_plane_once():
    from jax.profiler import ProfileData

    from bench.lib import spec, system, traffic
    from bench.tests import smoke

    cell = smoke.cell()
    sys_ = system.build(cell.config, spec.reference_module(cell.config),
                        seed=5, chips=1)
    system.warm(sys_, traffic.grid_lengths(cell.traffic))
    sched = traffic.schedule(cell.traffic, rate_per_s=8.0, lead_in_s=0.1,
                             seconds=1.0, seed=2**33 + 1,
                             vocab_size=sys_.hp["v"])
    run = system.serve(sys_, sched, lead_in_s=0.1, seconds=1.0,
                       profile_at=(-0.1, 2.0), tracer=Tracer(1 << 16))
    path = run.rec.profile.xplane()
    assert path is not None
    host = [e.name for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU" for line in p.lines
            for e in line.events]
    run.rec.profile.cleanup()
    names = set(host)
    for want in PHASES + ("sched.stage", "sched.adopt", "loop.idle"):
        assert want in names, want
    assert "request" not in names
    profiled = [t for t in run.rec.ticks if t.profiled]
    assert profiled and host.count("engine.decode_tick") == len(profiled)


def test_traced_smoke_run_reads_every_span_metric():
    from bench.tests import smoke

    line = smoke.run_line(smoke.cell(), trace=True, seconds=1.0)
    assert line["correct"] is True
    for name in NEW_METRICS:
        assert name in line["metrics"], name
        assert math.isfinite(line["metrics"][name]["value"])
