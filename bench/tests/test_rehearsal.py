"""CPU rehearsal of the harness: the traffic generator, the due-time
adapter through ``run_continuous`` at a smoke size, the metric arithmetic,
and the refusal to measure without a TPU."""

import json
import math
import os
import shutil
import subprocess
import sys
from collections import Counter

import numpy as np
import pytest

from bench.lib import spec, system, timings, traffic
from bench.lib.system import Served
from bench.tests import smoke

CHAT = spec._load_json(spec.BENCH / "traffic" / "chat.json")


def test_traffic_is_deterministic_per_seed():
    a = traffic.schedule(CHAT, rate_per_s=2.0, lead_in_s=5, seconds=30,
                         seed=2**40 + 7, vocab_size=1000)
    b = traffic.schedule(CHAT, rate_per_s=2.0, lead_in_s=5, seconds=30,
                         seed=2**40 + 7, vocab_size=1000)
    c = traffic.schedule(CHAT, rate_per_s=2.0, lead_in_s=5, seconds=30,
                         seed=7, vocab_size=1000)
    key = [(r.offset_s, r.new_tokens, r.prompt.tobytes()) for r in a]
    assert key == [(r.offset_s, r.new_tokens, r.prompt.tobytes()) for r in b]
    assert key != [(r.offset_s, r.new_tokens, r.prompt.tobytes()) for r in c]


def test_every_seed_offers_the_window_the_same_work():
    def work(seed):
        s = traffic.schedule(CHAT, rate_per_s=2.0, lead_in_s=5, seconds=30,
                             seed=seed, vocab_size=1000)
        return Counter((len(r.prompt), r.new_tokens) for r in s
                       if r.in_window), s

    w1, s1 = work(1)
    w2, s2 = work(2**33 + 5)
    assert w1 == w2 and sum(w1.values()) == 60
    # One schedule, in one order: the seeds differ in token ids alone.
    shape = lambda s: [(len(r.prompt), r.new_tokens, r.offset_s,
                        r.in_window) for r in s]
    assert shape(s1) == shape(s2)
    assert any(not np.array_equal(a.prompt, b.prompt)
               for a, b in zip(s1, s2))


def test_lengths_on_the_grid_and_inside_the_window():
    s = traffic.schedule(CHAT, rate_per_s=3.0, lead_in_s=4, seconds=20,
                         seed=3, vocab_size=500)
    grid = set(CHAT["prompt_grid"])
    assert all(len(r.prompt) in grid for r in s)
    assert all(16 <= r.new_tokens <= 256 for r in s)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 500 for r in s)
    lead = [r.offset_s for r in s if not r.in_window]
    win = [r.offset_s for r in s if r.in_window]
    assert len(lead) == 12 and len(win) == 60
    assert 0 < min(lead) and max(lead) < 4 <= min(win) and max(win) < 24


def _served(due, token_t, rid=0, window=True):
    r = Served(rid, np.zeros(4, np.int32), len(token_t), due, window)
    r.token_t = list(token_t)
    return r


def test_metric_arithmetic_on_synthetic_timestamps():
    reqs = [_served(10.0, [10.1, 10.2, 10.4], 0),
            _served(10.5, [11.0, 11.1], 1),
            _served(9.0, [9.5, 10.05, 12.5], 2, window=False)]
    win = [r for r in reqs if r.in_window]
    m = timings.end_to_end(win, 10.0, 12.0, setup_s=42.0)
    # ttft: 0.1 and 0.5; p90 by linear interpolation: 0.1 + 0.9 * 0.4.
    assert m["ttft_p90_ms"] == pytest.approx(460.0)
    # gaps 0.1, 0.2, 0.1 of the window's requests only.
    assert m["itl_mean_ms"] == pytest.approx(400.0 / 3)
    assert m["itl_p99_ms"] == pytest.approx(1e3 * np.percentile(
        [0.1, 0.2, 0.1], 99))
    # the window's requests' tokens inside [10, 12): 3 + 2; the lead-in
    # request's token at 10.05 does not count.
    assert m["out_tok_s"] == pytest.approx(5 / 2.0)
    assert m["setup_s"] == 42.0


@pytest.fixture(scope="module")
def smoke_system():
    from bench.lib.cell import devices_for

    cell = smoke.cell()
    devices_for(1, require_tpu=False)
    sys_ = system.build(cell.config, spec.reference_module(cell.config),
                        seed=5, chips=1)
    system.warm(sys_, traffic.grid_lengths(cell.traffic))
    return cell, sys_


def test_due_adapter_drives_run_continuous_open_loop(smoke_system):
    cell, sys_ = smoke_system
    sched = traffic.schedule(cell.traffic, rate_per_s=30.0, lead_in_s=0.2,
                             seconds=0.8, seed=11, vocab_size=sys_.hp["v"])
    run = system.serve(sys_, sched, lead_in_s=0.2, seconds=0.8)
    assert len(run.reqs) == len(sched) == 30
    for r in run.reqs:
        # Nothing is visible, admitted or answered before it is due.
        assert r.due <= r.visible_t <= r.admit_t0 < r.token_t[0]
        assert len(r.token_t) == r.new_tokens == len(r.output) - len(
            r.prompt)
        assert np.all(np.diff(r.token_t) >= 0) and r.done_t >= r.token_t[-1]
    # Arrivals were spread over the wall clock, not all at tick 0.
    dues = sorted(r.due for r in run.reqs)
    assert dues[-1] - dues[0] > 0.5
    assert run.stats["fused_decisions"] > 0
    assert len(run.rec.decisions) >= 1
    assert all(t.positions for t in run.rec.ticks)


def _run_bench(cwd, env_extra=None, timeout=300):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ds7b-l16x2.chat",
         "--seed", "3", "--seconds", "2"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout)


def test_no_tpu_means_no_result():
    res = _run_bench(spec.ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr


def test_benchmark_without_the_program_fails(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_bench(tmp_path)
    assert res.returncode != 0
    assert res.stdout.strip() == ""


def test_result_line_names_the_device(smoke_system, capsys):
    from bench.lib import cell as cellmod

    cell, _ = smoke_system
    rc = cellmod.run(cell, seed=4, seconds=0.6, trace=False,
                     process_start=0.0, require_tpu=False)
    out, err = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert "kind" in line["device"]
    assert line["correct"] is True
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(math.isfinite(v["value"]) for v in line["metrics"].values())
    # The numbers compared are the last lines on standard error.
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert [t.split()[0] for t in tail] == list(line["checks"])
