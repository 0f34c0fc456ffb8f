#!/usr/bin/env python3
"""Record a small profiler trace on the chip, for the trace-reduction test.

    python3 bench/tools/record_trace.py --out /tmp/small_trace

Runs two small jitted programs (one named ``tick``, one ``prefill``) under
host annotations named like the serving loop's spans, with idle gaps
between them, profiles it with ``jax.profiler``, and prints which planes,
lines and event names the trace holds.  The ``.xplane.pb`` it writes is
what ``bench/tests/data/small.xplane.pb`` was recorded from.
"""

from __future__ import annotations

import argparse
import glob
import shutil
import sys
import time
from collections import Counter
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"record_trace: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1

    def tick(w, x):
        return jnp.tanh(x @ w) @ w.T

    def prefill(w, x):
        return (x @ w).sum(axis=0)

    tick_j, prefill_j = jax.jit(tick), jax.jit(prefill)
    w = jnp.ones((2048, 2048), jnp.bfloat16)
    x4 = jnp.ones((4, 2048), jnp.bfloat16)
    x256 = jnp.ones((256, 2048), jnp.bfloat16)
    tick_j(w, x4).block_until_ready()
    prefill_j(w, x256).block_until_ready()

    out = Path(args.out)
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(str(out))
    window = jax.profiler.TraceAnnotation("bench.window")
    window.__enter__()
    for i in range(3):
        with jax.profiler.TraceAnnotation("engine.admit"):
            prefill_j(w, x256).block_until_ready()
            time.sleep(0.002)
        for _ in range(2):
            with jax.profiler.TraceAnnotation("engine.decode_tick"):
                tick_j(w, x4).block_until_ready()
        time.sleep(0.003)       # a gap no span covers
    with jax.profiler.TraceAnnotation("fabric.map_event"):
        time.sleep(0.001)
    window.__exit__(None, None, None)
    jax.profiler.stop_trace()

    path = glob.glob(str(out / "plugins/profile/*/*.xplane.pb"))[0]
    print(f"trace: {path} ({Path(path).stat().st_size} bytes)")
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            names = Counter(e.name for e in line.events)
            print(f"  line {line.name!r}: {sum(names.values())} events, "
                  f"{dict(names.most_common(6))}")
            for e in list(line.events)[:2]:
                print(f"    {e.name!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns} stats={list(e.stats)[:6]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
