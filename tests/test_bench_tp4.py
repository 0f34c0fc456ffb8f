"""The four-chip benchmark cell ``ds7b-tp4.chat``: deepseek-llm-7b whole on
one tensor-parallel replica over a 1x4 slice.

* the cell loads by name, at the published sizes with nothing cut, on a
  mesh of as many chips as it asks for, and its traffic fits a slot;
* a chip's share (weights, page pool and one dense view at a full lane
  bucket) fits one v5e;
* the same layout at smoke widths, served through the harness on four
  virtual CPU devices, matches the float32 reference, and its decode ticks
  carry ``chips``, ``lanes``, ``view_bytes`` and ``exchange_bytes`` (an
  unmeshed engine's carry none, and reading the exchange compiles nothing); a
  host-path decision after a fused tick takes the registers the tick left
  over four devices on one, and the next tick takes them back to the mesh
  without a compile; a run at a light load, where host-path decisions
  follow fused ticks and each other, compiles nothing after its set-up;
* the three ``tp.*`` readers read nothing from spans without those args,
  and the two per-lane readers read the same whatever lane bucket the
  ticks ran at.
"""

import json
import math
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench.lib import spec, traffic
from bench.tests import smoke

CELL = "ds7b-tp4.chat"
V5E_HBM_BYTES = 16_909_334_528        # one v5e chip, as the device reports it
READERS = ("tp.tick.wall_p50_ms", "tp.view_mb_per_lane",
           "tp.exchange_kb_per_lane")
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "vocab_size", "rope_theta", "torch_dtype",
          "hidden_act", "model_type", "tie_word_embeddings")


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def _entry(kind, name):
    bench = spec._load_json(spec.ROOT / "BENCHMARK.json")
    return next(e for e in bench[kind] if e["name"] == name)


def test_tp4_cell_loads_by_name(cell):
    w = _entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == ("deepseek-7b-tp4",
                                                       "chat", 4)
    assert cell.chips == 4
    assert cell.params["rate_per_s"] > 0
    assert {m["name"] for m in cell.per_layer} >= set(READERS)


def test_tp4_sizes_are_the_published_model(cell):
    one_chip = spec._load_json(spec.ROOT / "bench/configs/"
                               "deepseek-7b-l16x2.json")
    for key in WIDTHS:
        assert cell.config[key] == one_chip[key], key
    assert cell.config["num_hidden_layers"] == 30
    assert one_chip["published"]["num_hidden_layers"] == 30
    assert cell.config["rms_norm_eps"] == 1e-6
    assert _entry("configs", "deepseek-7b-tp4")["reduced"] == []


def test_tp4_mesh_uses_every_chip(cell):
    serving = cell.config["serving"]
    shape = [int(x) for x in serving["mesh"].split("x")]
    assert math.prod(shape) == cell.chips
    assert serving["replicas"] == 1


def test_tp4_traffic_fits_a_slot(cell):
    assert traffic.max_total(cell.traffic) <= cell.config["serving"]["max_len"]


def test_tp4_chip_share_fits_a_v5e(cell):
    """Weights, page pool and one dense K/V view at a full lane bucket, per
    chip, in bf16."""
    c, s = cell.config, cell.config["serving"]
    L, d, f, v = (c[k] for k in ("num_hidden_layers", "hidden_size",
                                 "intermediate_size", "vocab_size"))
    kv_bytes = c["num_key_value_heads"] * (d // c["num_attention_heads"]) * 2
    weights = (2 * v * d + L * (4 * d * d + 3 * d * f)) * 2
    pages = s["max_batch"] * s["max_len"] // s["page_size"] + 1
    pool = L * pages * s["page_size"] * kv_bytes * 2
    view = L * s["max_batch"] * s["max_len"] * kv_bytes * 2
    chips = cell.chips
    assert weights / chips + pool / chips + view / chips < V5E_HBM_BYTES
    assert c["sizes_per_chip"]["page_pool_bytes"] == pool // chips
    assert c["sizes_per_chip"]["dense_view_bytes_at_8_lanes"] == view // chips


@pytest.mark.parametrize("name", READERS)
def test_tp4_reader_reads_nothing_without_its_arg(name):
    """An unmeshed tick, or a program that records none of the args."""
    args = {"active": 2, "fused": True, "pages_reserved": 4,
            "pages_written": 3, "attn": "paged"}
    run = SimpleNamespace(spans=[("engine.decode_tick", 1.0, 0.01, args),
                                 ("tick.wait", 1.001, 0.008, {})])
    assert spec.metric_reader(name)(run) is None


@pytest.mark.parametrize("name,arg,scale", [
    ("tp.view_mb_per_lane", "view_bytes", 1e6),
    ("tp.exchange_kb_per_lane", "exchange_bytes", 1e3)])
def test_tp4_per_lane_reader_ignores_the_bucket(name, arg, scale):
    """Ticks at lane buckets 4 and 8 read as one per-lane value, however
    many of each the window holds."""
    def tick(active, lanes):
        return ("engine.decode_tick", 1.0, 0.05,
                {"active": active, "chips": 4, "lanes": lanes,
                 arg: lanes * 1000})
    read = spec.metric_reader(name)
    for mix in ([tick(3, 4)], [tick(3, 4), tick(5, 8), tick(6, 8)],
                [tick(7, 8)] * 3 + [tick(4, 4)]):
        assert read(SimpleNamespace(spans=mix)) == 1000 / scale


# The tp4 layout at smoke widths: one replica on a 1x4 mesh, 8 lanes.  Run
# on four virtual CPU devices in a fresh process (the device count is
# fixed at start-up).
SHARDED = r'''
import io, json, sys, traceback
sys.path[:0] = [{root!r}, {src!r}]
import jax
import numpy as np
from bench.lib import cell as cellmod, spec, system, traffic
from bench.tests import smoke
from repro.obs import Tracer

compiles = []


def on_compile(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        compiles.append(any(f.name == "compiled_wire_bytes"
                            for f in traceback.extract_stack()))


jax.monitoring.register_event_duration_secs_listener(on_compile)
serving = {{"replicas": 1, "speeds": [1.0], "mesh": "1x4", "max_batch": 8}}
meshed = smoke.cell(serving=serving, params={{"rate_per_s": 80.0}})
meshed.chips = 4


def ticks(cell):
    sys_ = system.build(cell.config, spec.reference_module(cell.config), 5,
                        cell.chips)
    system.warm(sys_, traffic.grid_lengths(cell.traffic))
    sched = traffic.schedule(cell.traffic, rate_per_s=80.0, lead_in_s=0.2,
                             seconds=0.6, seed=2**33 + 5,
                             vocab_size=sys_.hp["v"])
    run = system.serve(sys_, sched, lead_in_s=0.2, seconds=0.6,
                       tracer=Tracer(1 << 16))
    # A fused tick leaves the fabric's registers where it ran; a host-path
    # decision (a Mosaic kernel on a TPU) then takes them on one device.
    fab, event = sys_.fabric, (np.ones(1), np.ones((1, len(sys_.replicas))))
    eng = sys_.replicas[0].engine
    eng.admit(np.zeros(8, np.int32), 3)
    n = len(compiles)
    eng.decode_tick(event + (fab,))
    devices = [len(fab._avail.sharding.device_set)]
    fab.map_event(*event)
    devices.append(len(fab._avail.sharding.device_set))
    eng.decode_tick(event + (fab,))
    return ([a for name, _, _, a in run.spans
             if name == "engine.decode_tick"], devices, len(compiles) - n)


def window_compiles():
    """What a run of the meshed cell at a light load compiles after its
    set-up: the loop's host-path decisions, with the fleet idle, follow
    fused ticks and each other."""
    cell = smoke.cell(serving=serving, params={{"rate_per_s": 6.0}})
    cell.chips = 4
    err = io.StringIO()
    rc = cellmod.run(cell, seed=2**33 + 9, seconds=1.5, trace=False,
                     process_start=0.0, require_tpu=False,
                     out=io.StringIO(), err=err)
    assert rc == 0, err.getvalue()
    line, = [x for x in err.getvalue().splitlines()
             if x.startswith("compiles after set-up: ")]
    return json.loads(line.split(": ", 1)[1])


mesh_ticks, mesh_devices, mesh_new = ticks(meshed)
plain_ticks, _, _ = ticks(smoke.cell())
out = {{"line": smoke.run_line(meshed, trace=True), "mesh_ticks": mesh_ticks,
        "plain_ticks": plain_ticks, "register_devices": mesh_devices,
        "compiles_after_host_path": mesh_new,
        "window_compiles": window_compiles(),
        "compiles": len(compiles), "recompiles": sum(compiles)}}
print(json.dumps(out))
'''


def test_tp4_layout_matches_reference_and_counts_its_tick():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SHARDED.format(root=str(spec.ROOT), src=str(spec.ROOT / "src"))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])

    line = out["line"]
    assert line["device"]["count"] == 4
    assert line["correct"] is True, line["checks"]
    for name in READERS:
        assert name in line["metrics"], name

    # Per chip: 2 layers x (K, V) x 64 tokens x 1 of 4 KV heads x 16 x f32.
    cfg = smoke.CONFIG
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    lane = (cfg["num_hidden_layers"] * 2 * cfg["serving"]["max_len"]
            * cfg["num_key_value_heads"] // 4 * hd * 4)
    ticks = out["mesh_ticks"]
    assert ticks
    for t in ticks:
        bucket = 1 << (t["active"] - 1).bit_length()
        assert t["chips"] == 4
        assert t["lanes"] == bucket
        assert t["view_bytes"] == bucket * lane
        assert t["exchange_bytes"] > 0
    assert out["register_devices"] == [4, 1]
    assert out["compiles_after_host_path"] == 0   # the next tick is warm
    assert out["window_compiles"] == {"traces": 0, "compiles": 0,
                                      "cache_hits": 0}
    # Reading a bucket's exchange finds the executable its tick compiled.
    assert out["compiles"] > 0 and out["recompiles"] == 0
    assert out["plain_ticks"]
    for t in out["plain_ticks"]:
        assert not ({"chips", "lanes", "view_bytes", "exchange_bytes"}
                    & set(t)), t
