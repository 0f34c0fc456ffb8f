#!/usr/bin/env python3
"""Bring-up smoke run on TPU: deepseek-7b at its published widths, served
through the launcher's paged fused-scheduler path.

    python chip_smoke.py              # one chip: 16 of 30 layers, 2 replicas
    python chip_smoke.py --chips 4    # 4 chips: 30 layers, one 1x4 replica

It calls ``repro.launch.serve.main`` (what ``python -m repro.launch.serve
--full --paged --fused-scheduler ...`` runs) in this one process, checks
every served request against the model's full forward pass on the same
replica (``check_against_reference``: each served token's logit within
``ORACLE_TOL_ULPS`` of the reference's top logit), and prints one fact per
line.  The last line of its output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
With no TPU, outside the repository, or on any failed phase it exits
non-zero and prints no such line.  The weights are random, from seed 0.

One chip: published widths (d_model 4096, 32 heads of 128, 32 KV heads,
d_ff 11008, vocab 102400, bf16), depth cut to 16 layers so the weights
(8.15 GB) leave room on a 16 GB v5e for two replicas' page pools (4 slots
of 1024 tokens each) and the decode tick's transient.  Four chips: all 30
layers, one replica on a 1x4 (data x model) slice.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

# Each phase: the launcher arguments that define it, and the chips it needs.
CONFIGS = {
    1: ["--num-layers", "16", "--replicas", "2", "--max-len", "1024",
        "--max-batch", "4"],
    4: ["--sharded", "--mesh-shapes", "1x4", "--max-len", "3072",
        "--max-batch", "8"],
}
COMMON = ["--arch", "deepseek-7b", "--full", "--paged", "--fused-scheduler",
          "--requests", "8", "--new-tokens", "32", "--prompt-lens", "128,512",
          "--page-size", "16"]


def fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(CONFIGS), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch import serve
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        return fail(f"the repository's code is not beside this script ({e})")

    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return fail(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < args.chips:
        return fail(f"--chips {args.chips} but JAX sees {len(devices)}")
    print(f"device: {dev.device_kind} x{len(devices)} ({dev.platform})")
    print(f"compile cache: {enable_compile_cache()}")

    compiles = {"n": 0, "s": 0.0, "hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
            compiles["s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            compiles["hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    argv = COMMON + CONFIGS[args.chips]
    print("launcher: python -m repro.launch.serve " + " ".join(argv))
    res = serve.main(argv)      # SystemExit (non-zero) on a failed phase

    cfg, front, fabric = res["cfg"], res["front"], res["fabric"]
    stats, requests, outs = res["stats"], res["requests"], res["outputs"]
    print(f"config: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads} "
          f"kv_heads={cfg.num_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} {cfg.param_dtype}")
    print(f"cut: depth {cfg.num_layers} of "
          f"{serve.get_config(cfg.name).num_layers} layers, widths as "
          f"published")
    engine = front.replicas[0].engine
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(engine.params))
    print(f"parameter bytes: {param_bytes} ({len(front.replicas)} "
          f"replica(s), one weight tree per slice)")
    print(f"compiles: {compiles['n']} ({compiles['hits']} from the "
          f"persistent cache), {compiles['s']:.1f} s")
    print(f"fabric.backend_effective: {fabric.backend_effective}")
    print(f"scheduling decisions: {stats['fused_decisions']} fused in-tick, "
          f"{stats['host_decisions']} host")

    # Request 0 was checked by the launcher; check every request here.
    served, worst = 0, 0.0
    for i, ((prompt, nt), out) in enumerate(zip(requests, outs)):
        seq = out[0]
        if len(seq) != len(prompt) + nt or not np.array_equal(
                seq[:len(prompt)], prompt):
            return fail(f"request {i}: served sequence has the wrong shape")
        if not ((seq >= 0) & (seq < cfg.vocab_size)).all():
            return fail(f"request {i}: token ids outside the vocabulary")
        served += nt
        gap, diverged = serve.check_against_reference(cfg, engine, prompt,
                                                      seq)
        if diverged is not None:
            return fail(f"request {i} disagrees with the full-forward "
                        f"reference: {diverged}")
        worst = max(worst, gap)
    print(f"tokens served: {served} in {len(requests)} requests, "
          f"{stats['ticks']} ticks")
    print(f"oracle: all {len(requests)} requests within "
          f"{serve.ORACLE_TOL_ULPS} ulps of the full-forward reference's top "
          f"logit on replica 0 (largest gap {worst:.1f} ulps)")

    if fabric.backend_effective != "fused":
        return fail(f"fabric ran {fabric.backend_effective!r}, not 'fused'")
    if stats["fused_decisions"] == 0:
        return fail("no scheduling decision ran inside the decode tick")
    if stats["allocated"] != stats["freed"]:
        return fail(f"pages leaked: {stats['allocated']} allocated, "
                    f"{stats['freed']} freed")

    for d in devices[:args.chips]:
        mem = d.memory_stats() or {}
        if "peak_bytes_in_use" not in mem:
            return fail(f"device {d.id} reports no peak_bytes_in_use")
        print(f"peak_bytes_in_use: {mem['peak_bytes_in_use']} of "
              f"{mem.get('bytes_limit')} (device {d.id})")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
