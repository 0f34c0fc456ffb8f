"""End-to-end metrics from the harness's timestamps (host clock, seconds).

Every request due in the window counts, and every gap between two of its
consecutive output tokens; no metric is a median of chunks.

``out_tok_s`` counts the window's own requests' tokens delivered inside the
window [w0, w1), over its length.  Every such request is due at or after
w0, so a token served sooner can only move into the window, never out of
it: the count never falls as the system gets faster.  Below the knee it
reads the offered tokens less those still to come at w1.  Lead-in
requests' tokens never count: a slower system would serve more of them
inside the window and read higher.
"""

from __future__ import annotations

import numpy as np


def ttfts_s(reqs) -> np.ndarray:
    """Due time to first token, per request."""
    return np.array([r.token_t[0] - r.due for r in reqs])


def gaps_s(reqs) -> np.ndarray:
    """Every gap between consecutive output tokens of ``reqs``."""
    parts = [np.diff(np.asarray(r.token_t)) for r in reqs]
    return np.concatenate(parts) if parts else np.zeros(0)


def tokens_in(reqs, w0: float, w1: float) -> int:
    """Output tokens of ``reqs`` delivered inside [w0, w1)."""
    return int(sum(((np.asarray(r.token_t) >= w0)
                    & (np.asarray(r.token_t) < w1)).sum() for r in reqs))


def end_to_end(window_reqs, w0: float, w1: float, setup_s: float) -> dict:
    """The five end-to-end metrics, by name."""
    g = gaps_s(window_reqs)
    return {
        "ttft_p90_ms": 1e3 * float(np.percentile(ttfts_s(window_reqs), 90)),
        "itl_mean_ms": 1e3 * float(g.sum() / len(g)),
        "itl_p99_ms": 1e3 * float(np.percentile(g, 99)),
        "out_tok_s": tokens_in(window_reqs, w0, w1) / (w1 - w0),
        "setup_s": float(setup_s),
    }


def lateness_ms(window_reqs) -> dict:
    """How late the loop found requests due: visible time minus due time
    (the generator is the due-time hook; the loop polls it once an
    iteration, so a long step shows here)."""
    late = np.array([r.visible_t - r.due for r in window_reqs]) * 1e3
    return {"p50": float(np.median(late)),
            "p99": float(np.percentile(late, 99)),
            "max": float(late.max())}
