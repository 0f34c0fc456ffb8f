"""``out_tok_s`` on the cells' real schedules with synthetic timestamps.

Each request's k-th token lands at its due time + TTFT + k * ITL, with no
queueing, so a smaller ITL is a faster system serving the same schedule.
The metric must never read lower for the faster one.
"""

import numpy as np
import pytest

from bench.lib import spec, timings, traffic
from bench.lib.system import Served

TTFT_S = 0.1
ITLS_S = (0.005, 0.0103, 0.0125, 0.02, 0.0375, 0.06)
BENCHMARK = spec._load_json(spec.ROOT / "BENCHMARK.json")
SECONDS = float(BENCHMARK["run_seconds"])
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def _served(cell_name: str, itl_s: float):
    """The cell's schedule, every token timed at ``itl_s``; the window."""
    cell = spec.load_cell(cell_name)
    p = cell.params
    sched = traffic.schedule(cell.traffic, rate_per_s=p["rate_per_s"],
                             lead_in_s=p["lead_in_s"], seconds=SECONDS,
                             seed=2**33 + 1, vocab_size=64)
    reqs = []
    for r in sched:
        s = Served(r.rid, r.prompt, r.new_tokens, r.offset_s, r.in_window)
        s.token_t = list(r.offset_s + TTFT_S + itl_s * np.arange(r.new_tokens))
        reqs.append(s)
    return reqs, p["lead_in_s"], p["lead_in_s"] + SECONDS


def _out_tok_s(reqs, w0, w1) -> float:
    win = [r for r in reqs if r.in_window]
    return timings.end_to_end(win, w0, w1, setup_s=1.0)["out_tok_s"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_out_tok_s_never_falls_as_the_system_gets_faster(cell_name):
    readings = [_out_tok_s(*_served(cell_name, itl)) for itl in ITLS_S]
    # ITLS_S rise, so the readings may only fall along it.
    assert all(b <= a for a, b in zip(readings, readings[1:])), readings


def test_tp4_chat_fast_tick_reads_the_offered_load():
    reqs, w0, w1 = _served("ds7b-tp4.chat", 0.0103)
    offered = sum(r.new_tokens for r in reqs if r.in_window) / (w1 - w0)
    assert offered == pytest.approx(86.84)
    assert _out_tok_s(reqs, w0, w1) == pytest.approx(offered)
    slow = _out_tok_s(*_served("ds7b-tp4.chat", 0.0375))
    assert slow < offered
    assert slow == pytest.approx(84.2, abs=0.5)


def test_tp4_chat_lead_in_spill_counts_only_in_the_all_requests_rate():
    """Why the rule changed: counting every request's tokens, as the sweep's
    ``delivered_tok_s`` still does, adds the lead-in answers that run on
    past w0, and so reads the slower tick higher."""
    def all_tok_s(reqs, w0, w1):
        return timings.tokens_in(reqs, w0, w1) / (w1 - w0)

    fast = _served("ds7b-tp4.chat", 0.0103)
    slow = _served("ds7b-tp4.chat", 0.0375)
    reqs, w0, w1 = slow
    spill = timings.tokens_in([r for r in reqs if not r.in_window], w0, w1)
    assert spill > 0
    assert all_tok_s(*slow) == pytest.approx(
        _out_tok_s(*slow) + spill / (w1 - w0))
    assert all_tok_s(*fast) == pytest.approx(86.84)
    assert all_tok_s(*slow) == pytest.approx(91.3, abs=0.1)
    assert _out_tok_s(*slow) < _out_tok_s(*fast)
