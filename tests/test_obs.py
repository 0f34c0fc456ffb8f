"""repro.obs: tracer ring, histogram bucket edges, Chrome export schema,
device-counter inertness.

The load-bearing claim is the last one: the instrumented ``MappingFabric``
(tracer + metrics + device-resident counters all enabled) stays
slot-for-slot bit-identical to the ``heft_rt_numpy`` oracle — the paper's
hardware counters don't perturb the schedule, and neither do ours.
"""

import json
import math

import numpy as np

from _hypothesis_compat import given, settings, st

from repro.core import heft_rt_numpy
from repro.obs import (
    COUNTER_NAMES,
    HIST_BUCKETS,
    HIST_MIN_S,
    Histogram,
    LOG_LEVELS,
    MetricsRegistry,
    NULL_TRACER,
    Stopwatch,
    TraceEvent,
    Tracer,
    accumulate_counters_np,
    counters_dict,
    get_logger,
    time_s,
    validate_chrome_trace,
)
from repro.obs.trace import NULL_SPAN
from repro.sched_integration import MappingFabric

settings.register_profile("ci", max_examples=20, deadline=None)
settings.load_profile("ci")


# ---------------------------------------------------------------------------
# Histogram bucket edges (property: edge[i] <= v < edge[i+1], ns → s)
# ---------------------------------------------------------------------------

@given(v=st.floats(1e-10, 2000.0))
def test_histogram_bucket_edge_invariant(v):
    edges = Histogram.bucket_edges()
    i = Histogram.bucket_index(v)
    assert 0 <= i < HIST_BUCKETS
    if v <= HIST_MIN_S:
        assert i == 0                          # clamp below the 1 ns floor
    elif v >= edges[-1]:
        assert i == HIST_BUCKETS - 1           # clamp above the top edge
    else:
        assert edges[i] <= v < edges[i + 1]


def test_histogram_exact_power_of_two_edges():
    edges = Histogram.bucket_edges()
    assert len(edges) == HIST_BUCKETS + 1
    assert edges[0] == HIST_MIN_S
    assert edges[-1] > 1000.0                  # the axis really spans ns → s
    for i in range(HIST_BUCKETS):
        # an exact edge value belongs to the bucket it opens
        assert Histogram.bucket_index(edges[i]) == min(i, HIST_BUCKETS - 1)
        # just below the edge belongs to the previous bucket
        below = edges[i] * (1 - 1e-12)
        assert Histogram.bucket_index(below) == max(i - 1, 0)


def test_histogram_record_and_percentiles():
    h = Histogram()
    for v in (1e-9, 9.144e-9, 1e-6, 1e-3, 1.0):
        h.record(v)
    assert h.count == 5
    assert h.min == 1e-9 and h.max == 1.0
    assert math.isclose(h.sum, 1e-9 + 9.144e-9 + 1e-6 + 1e-3 + 1.0)
    p50 = h.percentile(50)
    edges = Histogram.bucket_edges()
    i = Histogram.bucket_index(1e-6)
    assert edges[i] <= p50 <= edges[i + 1]     # median bounded by its bucket
    assert h.percentile(99) <= h.max
    snap = h.snapshot()
    assert snap["count"] == 5 and sum(snap["buckets"].values()) == 5


def test_histogram_weighted_record():
    h = Histogram()
    h.record(2e-6, n=64)                       # one batched event, 64 decisions
    assert h.count == 64
    assert math.isclose(h.sum, 2e-6 * 64)
    assert h.buckets[Histogram.bucket_index(2e-6)] == 64


# ---------------------------------------------------------------------------
# Counters / gauges / registry
# ---------------------------------------------------------------------------

def test_registry_labels_and_types():
    m = MetricsRegistry()
    m.counter("x", backend="jit").inc()
    m.counter("x", backend="jit").inc(2)
    m.counter("x", backend="numpy").inc()
    assert m.counter("x", backend="jit").value == 3
    assert m.counter("x", backend="numpy").value == 1
    assert "x{backend=jit}" in m and len(m) == 2
    m.gauge("g").set(4.5)
    try:
        m.histogram("g")
    except TypeError:
        pass
    else:
        raise AssertionError("type mismatch must raise")
    snap = m.snapshot()
    assert snap["x{backend=jit}"] == 3 and snap["g"] == 4.5


def test_timing_helpers():
    _, dt = time_s(sum, range(10))
    assert dt >= 0.0
    h = Histogram()
    with Stopwatch(h, n=4) as sw:
        sum(range(100))
    assert sw.elapsed_s >= 0.0 and sw.start_s > 0.0
    assert h.count == 4                        # weighted by n


def test_log_levels():
    assert LOG_LEVELS["silent"] > LOG_LEVELS["error"]
    log = get_logger("obs-test")
    log.info("hello")                          # must not raise
    import pytest

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_LOG", "bogus")
        with pytest.raises(ValueError):
            get_logger("obs-test2")
        mp.setenv("REPRO_LOG", "silent")
        assert not get_logger("obs-test3").isEnabledFor(LOG_LEVELS["error"])


# ---------------------------------------------------------------------------
# Tracer: ring wraparound, disabled no-op, Chrome export schema
# ---------------------------------------------------------------------------

def test_ring_wraparound_keeps_newest():
    tr = Tracer(capacity=8)
    for i in range(20):
        tr.instant(f"e{i}", ts_us=float(i))
    assert len(tr) == 8
    assert tr.dropped == 12
    names = [e.name for e in tr.events()]
    assert names == [f"e{i}" for i in range(12, 20)]   # oldest-first, newest 8


def test_disabled_tracer_is_noop():
    tr = Tracer(capacity=4, enabled=False)
    s1 = tr.span("a", k=1)
    s2 = tr.span("b")
    assert s1 is NULL_SPAN and s2 is NULL_SPAN         # singleton, no alloc
    with s1:
        pass
    tr.instant("x")
    tr.counter("c", v=1)
    tr.complete("y", 0.0, 1.0)
    tr.record(TraceEvent("z", "i", 0.0))
    assert len(tr) == 0 and tr.dropped == 0
    assert len(NULL_TRACER) == 0


def test_recorded_events_add_no_tracked_objects():
    """The ring is columns of plain values: a full ring gives the garbage
    collector nothing more to traverse."""
    import gc

    tr = Tracer(capacity=4096)
    gc.collect()
    before = len(gc.get_objects())
    for i in range(2000):
        with tr.span("s", i=i, tag="x"):
            pass
        tr.complete("c", 0.0, 1e-6, n=i)
    gc.collect()
    assert len(gc.get_objects()) - before < 100
    evs = tr.events()
    assert len(evs) == 4000 and [e.name for e in evs[:2]] == ["s", "c"]
    assert evs[0].args == {"i": 0, "tag": "x"} and evs[-1].args == {"n": 1999}


def test_phase_begin_and_gc_watch():
    import gc

    tr = Tracer()
    with tr.phase("p", ev=1) as sp:
        sp.set(n=2)
    opened = tr.begin("idle")
    opened.end(iterations=3)
    before = list(gc.callbacks)
    with tr.watch_gc():
        gc.collect()
    assert gc.callbacks == before
    spans = {e.name: e for e in tr.events()}
    assert spans["p"].args == {"ev": 1, "n": 2} and spans["p"].ph == "X"
    assert spans["idle"].args == {"iterations": 3}
    assert spans["host.gc"].args["generation"] == 2
    off = Tracer(enabled=False)
    assert off.phase("p") is NULL_SPAN and off.begin("q") is NULL_SPAN
    assert off.watch_gc() is NULL_SPAN
    with off.watch_gc():
        gc.collect()
    assert len(off) == 0


def test_chrome_export_schema(tmp_path):
    tr = Tracer()
    with tr.span("outer", tag="t"):
        tr.instant("mark")
    tr.counter("depth", ts_us=5.0, depth=3)
    tr.complete("hot", 0.0, 1e-3, n=2)
    m = MetricsRegistry()
    m.histogram("lat_s").record(1e-6, n=10)
    path = str(tmp_path / "trace.json")
    tr.export(path, metrics=m)
    with open(path) as f:
        obj = json.load(f)
    n = validate_chrome_trace(obj, require_names=["outer", "mark", "depth"])
    assert n == 4
    ts = [ev["ts"] for ev in obj["traceEvents"]]
    assert ts == sorted(ts)                            # export is time-ordered
    assert obj["metrics"]["lat_s"]["count"] == 10
    assert obj["otherData"]["dropped"] == 0
    # spans carry dur; counters carry their values
    phs = {ev["name"]: ev for ev in obj["traceEvents"]}
    assert phs["outer"]["ph"] == "X" and phs["outer"]["dur"] >= 0
    assert phs["depth"]["ph"] == "C" and phs["depth"]["args"]["depth"] == 3


def test_validate_rejects_malformed():
    import pytest

    for bad in (
        {"traceEvents": "nope"},
        {"traceEvents": [{"ph": "X", "ts": 0.0}]},            # no name
        {"traceEvents": [{"name": "a", "ph": "?", "ts": 0.0}]},
        {"traceEvents": [{"name": "a", "ph": "X", "ts": 0.0}]},  # X sans dur
        {"traceEvents": [{"name": "a", "ph": "i", "ts": "x"}]},
    ):
        with pytest.raises(ValueError):
            validate_chrome_trace(bad)


# ---------------------------------------------------------------------------
# Device counters: provably inert + correct values
# ---------------------------------------------------------------------------

def _random_event(rng, n, p):
    avg = rng.integers(0, 6, n).astype(np.float32)
    ex = rng.integers(1, 16, (n, p)).astype(np.float32)
    ex[rng.random(n) < 0.2] = np.inf
    avail = rng.integers(0, 8, p).astype(np.float32)
    return avg, ex, avail


@given(
    backend=st.sampled_from(["numpy", "jit", "pallas"]),
    n=st.integers(1, 24),
    p=st.integers(1, 6),
    seed=st.integers(0, 2**31 - 1),
)
def test_instrumented_fabric_bit_identical_to_oracle(backend, n, p, seed):
    """Tracer + metrics + device counters enabled: schedule unchanged."""
    rng = np.random.default_rng(seed)
    avg, ex, avail = _random_event(rng, n, p)
    fab = MappingFabric(p, backend=backend, tracer=Tracer(),
                        metrics=MetricsRegistry(), device_counters=True)
    got = fab.map_event(avg, ex, avail, update=False)
    want = heft_rt_numpy(avg, ex, avail)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_device_counters_match_host_twin_across_backends():
    rng = np.random.default_rng(3)
    events = [_random_event(rng, n, 4) for n in (3, 8, 11, 20)]
    ref = np.zeros(len(COUNTER_NAMES))
    for avg, ex, avail in events:
        _, a, _, _, na = heft_rt_numpy(avg, ex, avail)
        accumulate_counters_np(ref, a, na)
    want = counters_dict(ref)
    for backend in ("numpy", "jit", "pallas"):
        fab = MappingFabric(4, backend=backend, device_counters=True)
        for avg, ex, avail in events:
            fab.map_event(avg, ex, avail, update=False)
        got = fab.drain_counters()
        assert got == want, (backend, got, want)
        # drain(reset=True) zeroed the registers
        assert all(v == 0.0 for v in fab.drain_counters().values())


def test_fabric_dispatch_observability():
    tr, m = Tracer(), MetricsRegistry()
    fab = MappingFabric(4, backend="jit", tracer=tr, metrics=m,
                        device_counters=True)
    rng = np.random.default_rng(0)
    for n in (5, 5, 30):                       # 5→bucket 8 (x2), 30→bucket 32
        avg, ex, avail = _random_event(rng, n, 4)
        fab.map_event(avg, ex, avail, update=False)
    assert fab.retraces == 2                   # one per new bucketed shape
    assert m.counter("fabric.retraces").value == 2
    names = [e.name for e in tr.events()]
    assert names.count("fabric.retrace") == 2
    assert names.count("fabric.map_event") == 3
    hist = m.histogram("fabric.decision_s", backend="jit")
    assert hist.count == 5 + 5 + 30            # weighted per decision
    fab.grow(6)
    assert m.counter("fabric.resizes").value == 1
    assert m.gauge("fabric.num_pes").value == 6
    assert "fabric.resize" in {e.name for e in tr.events()}


def test_drain_requires_device_counters():
    import pytest

    fab = MappingFabric(2, backend="numpy")
    with pytest.raises(ValueError):
        fab.drain_counters()


# ---------------------------------------------------------------------------
# Serving / fleet integration stays bit-identical under instrumentation
# ---------------------------------------------------------------------------

def test_simulate_serving_identical_with_obs():
    from repro.sched_integration import default_fleet, make_requests
    from repro.sched_integration.serve_scheduler import (
        POLICIES,
        simulate_serving,
    )

    reqs = make_requests(30.0, 2.0, seed=5)
    base = simulate_serving(default_fleet(), reqs, POLICIES["heft_rt"](),
                            active_params=7e9)
    tr, m = Tracer(), MetricsRegistry()
    inst = simulate_serving(default_fleet(), reqs, POLICIES["heft_rt"](),
                            active_params=7e9, tracer=tr, metrics=m)
    assert base.achieved_rps == inst.achieved_rps
    assert base.p99_latency == inst.p99_latency
    np.testing.assert_array_equal(base.served_mask, inst.served_mask)
    np.testing.assert_array_equal(base.replica_util, inst.replica_util)
    depth = [e for e in tr.events() if e.name == "serve.queue_depth"]
    assert depth and all(e.ph == "C" for e in depth)
    ts = [e.ts for e in depth]
    assert ts == sorted(ts)                    # simulated-time ordering
    snap = m.snapshot()
    assert snap["serve.served"] == int(base.served_mask.sum())
    assert snap["serve.served"] + snap["serve.unserved"] == len(reqs)
    assert any(k.startswith("serve.replica_util{") for k in snap)


def test_fleet_controller_compat_trace_view():
    from repro.sched_integration.fleet import (
        FleetController,
        FleetControllerConfig,
        grown_replica_factory,
    )

    tr = Tracer()
    ctl = FleetController(FleetControllerConfig(grow_backlog_s=1.0,
                                                cooldown_s=0.0),
                          grown_replica_factory("a", (2, 2)), tracer=tr)
    ev = ctl.observe(1.0, queue_depth=9, backlog_s=5.0)
    assert ev is not None and ev.add
    ev2 = ctl.observe(2.0, queue_depth=0, backlog_s=0.0)
    assert ev2 is not None and ev2.remove
    # legacy tuple view preserved, derived from structured events
    assert [(t, k) for t, k, _ in ctl.trace] == [(1.0, "grow"), (2.0, "shrink")]
    assert all(isinstance(e, TraceEvent) for e in ctl.events)
    assert [e.name for e in ctl.events] == ["fleet.grow", "fleet.shrink"]
    assert ctl.events[0].ts == 1.0 * 1e6       # simulated-time stamp in µs
    # mirrored into the shared tracer
    assert [e.name for e in tr.events()] == ["fleet.grow", "fleet.shrink"]
