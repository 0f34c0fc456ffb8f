"""Cross-chip exchange (the collectives of a meshed tick's compiled
program): per chip, the collective wire bytes of the compiled tick for its
lane bucket, trip-weighted over the layer scan
(``launch/hlo_analysis.analyze_hlo``), per lane -- ``exchange_bytes`` over
``lanes`` of the live ``engine.decode_tick`` spans inside the window, their
median -- in KB of 10**3 bytes (traced run).  Per lane, so the reading does
not follow the bucket the traffic happens to fill.  Spans without
``exchange_bytes`` are not read."""

import numpy as np


def read(run):
    x = [args["exchange_bytes"] / args["lanes"]
         for name, _, _, args in run.spans
         if name == "engine.decode_tick" and args.get("active", 0) >= 1
         and "exchange_bytes" in args]
    return float(np.median(x)) / 1e3 if x else None
