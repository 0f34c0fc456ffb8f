"""The trace reduction, on a small trace recorded on a TPU v5e by
``bench/tools/record_trace.py``: three rounds of one ``prefill`` under an
``engine.admit`` annotation (with a 2 ms sleep inside it) and two ``tick``
runs under ``engine.decode_tick`` annotations, a 3 ms sleep under no
annotation after each round, and a 1 ms ``fabric.map_event`` at the end,
all inside ``bench.window``."""

from pathlib import Path

import pytest

from bench.lib import trace

SMALL = str(Path(__file__).parent / "data" / "small.xplane.pb")


@pytest.fixture(scope="module")
def raw():
    return trace.load(SMALL)


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(SMALL)


def test_names():
    assert trace.module_name("jit_tick_sched_counted(8323)") == \
        "tick_sched_counted"
    assert trace.module_name("jit__lambda(1)") == "_lambda"
    assert trace.op_name("%fusion.12 = bf16[4,2048]{1,0} fusion(%a)") == \
        "fusion.12"


def test_union_and_clip():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert trace.clip([(0, 2), (3, 8), (9, 10)], 1, 9) == [(1, 2), (3, 8)]


def test_recorded_trace_holds_what_was_run(raw):
    devices, host = raw
    assert len(devices) == 1
    names = [m[2] for m in devices[0].modules]
    assert names.count("prefill") == 3 and names.count("tick") == 6
    spans = [n for _, _, n in host]
    assert spans.count("engine.admit") == 3
    assert spans.count("engine.decode_tick") == 6
    assert spans.count("bench.window") == 1
    assert spans.count("fabric.map_event") == 1


def test_each_tick_lands_in_its_own_annotation(raw, summary):
    devices, host = raw
    ticks = [(s, e) for s, e, n in devices[0].modules if n == "tick"]
    anns = [(s, e) for s, e, n in host if n == "engine.decode_tick"]
    off = summary.offset_ns
    for (s, e), (hs, he) in zip(ticks, anns):
        assert hs <= s + off and e + off <= he
    assert summary.tick_device_s == pytest.approx(
        [(e - s) / 1e9 for s, e in ticks])


def test_busy_time_and_window(raw, summary):
    devices, host = raw
    (ws, we), = [(s, e) for s, e, n in host if n == "bench.window"]
    assert summary.window_s == pytest.approx((we - ws) / 1e9)
    # The recorded operations do not overlap, so their union is their sum.
    ops = devices[0].ops
    assert summary.busy_s == pytest.approx(sum(e - s for s, e, _ in ops)
                                           / 1e9)
    assert summary.module_s["tick"] == pytest.approx(
        sum(summary.tick_device_s))
    assert set(summary.module_s) == {"tick", "prefill"}
    top = summary.device_ops[0]
    assert top[0] == "prefill:convolution_reduce_fusion"


def test_idle_gaps_are_labelled_by_the_host_span(summary):
    labels = [g[0] for g in summary.idle_gaps]
    lengths = [g[1] for g in summary.idle_gaps]
    assert lengths == sorted(lengths, reverse=True)
    # The 3 ms sleeps under no annotation (plus the program launches
    # around them) are the longest gaps; the 2 ms sleeps inside admit next.
    assert labels[:3] == ["none"] * 3
    assert all(4e-3 < x < 5e-3 for x in lengths[:3])
    assert labels[3:6] == ["engine.admit"] * 3
    assert all(2e-3 < x < 3.5e-3 for x in lengths[3:6])
    assert "engine.decode_tick" in labels[6:]


def test_nested_operations_count_their_own_time():
    ops = [(0, 10, "while"), (1, 3, "a"), (4, 9, "b"), (5, 6, "c"),
           (12, 14, "d")]
    assert trace.self_times(ops) == [3, 2, 4, 1, 2]
    # A parent and its first child may start together.
    assert trace.self_times([(0, 10, "loop"), (0, 4, "x")]) == [6, 4]
