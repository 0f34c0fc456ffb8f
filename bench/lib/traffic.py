"""The one traffic generator: an open-loop schedule from a traffic file.

A schedule is a lead-in of requests due before the measured window (so the
window opens at steady occupancy) followed by the window's own requests.
Arrivals are Poisson at the cell's rate: the count in each interval is the
rate times its length, and the gaps between arrivals are exponential.

Every seed gets the same work.  The schedule -- each request's prompt
length, output length and due time, in order -- is drawn once from the
traffic file's ``sizes_seed``; ``--seed`` draws only the prompts' token
ids (and, elsewhere, the weights).  With no EOS the token ids change no
request's size, so the spread between runs measures the system rather than
the draw.

Traffic file keys:

* ``prompt_tokens``: ``{"dist": "lognormal", "median", "sigma", "min",
  "max"}`` or ``{"dist": "choice", "values": [...]}``;
* ``prompt_grid``: the allowed prompt lengths; a drawn length is rounded up
  to the next one (the program compiles one prefill per distinct length);
* ``output_tokens``: ``{"dist": "lognormal", ...}`` or ``{"dist":
  "uniform", "min", "max"}`` (inclusive);
* ``sizes_seed``: the seed of the schedule's sizes and arrivals.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Request:
    rid: int
    prompt: np.ndarray        # (S0,) int32
    new_tokens: int
    offset_s: float           # due time, seconds after the lead-in starts
    in_window: bool


def seed_sequence(seed: int) -> np.random.SeedSequence:
    """Any whole number (negative or beyond 64 bits too) as entropy."""
    return np.random.SeedSequence(int(seed) % (1 << 128))


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    dist = spec["dist"]
    if dist == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        x = np.clip(np.rint(x), spec["min"], spec["max"])
    elif dist == "uniform":
        x = rng.integers(spec["min"], spec["max"] + 1, n)
    elif dist == "choice":
        x = rng.choice(np.asarray(spec["values"]), n)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return x.astype(np.int64)


def _on_grid(lengths: np.ndarray, grid) -> np.ndarray:
    grid = np.sort(np.asarray(grid))
    idx = np.searchsorted(grid, lengths, side="left")
    if (idx >= len(grid)).any():
        raise ValueError(f"prompt length {lengths.max()} above the grid's "
                         f"largest {grid[-1]}")
    return grid[idx]


def _offsets(gaps: np.ndarray, length_s: float) -> np.ndarray:
    """n arrivals in [0, length_s) from n + 1 exponential gaps."""
    c = np.cumsum(gaps)
    return length_s * c[:-1] / c[-1]


def schedule(traffic: dict, *, rate_per_s: float, lead_in_s: float,
             seconds: float, seed: int, vocab_size: int) -> list[Request]:
    """The cell's requests, in due order."""
    fixed = np.random.default_rng(traffic["sizes_seed"])
    ids = np.random.default_rng(seed_sequence(seed))
    reqs: list[Request] = []
    start = 0.0
    for in_window, length_s in enumerate((lead_in_s, seconds)):
        n = int(round(rate_per_s * length_s))
        if in_window:
            n = max(1, n)
        prompts = _on_grid(_lengths(fixed, traffic["prompt_tokens"], n),
                           traffic["prompt_grid"])
        outs = _lengths(fixed, traffic["output_tokens"], n)
        offsets = start + _offsets(fixed.exponential(1.0, n + 1), length_s)
        for p, o, t in zip(prompts, outs, offsets):
            prompt = ids.integers(0, vocab_size, int(p), dtype=np.int32)
            reqs.append(Request(len(reqs), prompt, int(o), float(t),
                                bool(in_window)))
        start += length_s
    return reqs


def grid_lengths(traffic: dict) -> list[int]:
    return sorted(int(x) for x in traffic["prompt_grid"])


def max_total(traffic: dict) -> int:
    """Longest prompt plus the longest output the traffic can draw."""
    out = traffic["output_tokens"]
    return max(traffic["prompt_grid"]) + int(out["max"])
