"""Whether what the timed window served is correct.

Each number is compared with a limit of its own, and every run prints each
beside its limit:

* ``logit_gap``: on a sample of the window's finished requests, drawn from
  the seed and holding the one with the most generated tokens, the widest
  gap by which a served token's logit lies below the best logit of the
  configuration's plain float32 reference, run over the served sequence
  (this covers the prefill in ``admit``, and the paged decode tick with its
  in-tick argmax); the limit is the cell's ``limits.logit_gap``;
* ``heft_mismatch``: scheduling decisions (fused in the tick or on the
  host path) that differ from plain HEFT_RT replayed from the same inputs
  and the same starting registers; limit 0;
* ``misrouted``: requests admitted on another replica than the decision
  that mapped them named; limit 0;
* ``malformed``: window requests whose output is not their prompt followed
  by ``new_tokens`` ids inside the vocabulary, or that never finished;
  limit 0.
"""

from __future__ import annotations

import numpy as np

from . import heft
from .traffic import seed_sequence


def malformed(window_reqs, vocab_size: int) -> int:
    bad = 0
    for r in window_reqs:
        out = r.output
        if (out is None or len(out) != len(r.prompt) + r.new_tokens
                or not np.array_equal(out[:len(r.prompt)], r.prompt)
                or not ((out >= 0) & (out < vocab_size)).all()
                or len(r.token_t) != r.new_tokens):
            bad += 1
    return bad


def heft_mismatches(decisions, avail0) -> int:
    """Decisions, in call order, that differ from plain HEFT_RT given the
    registers they started from: ``avail0``, then each decision's own
    result.  A decision that matches hands on exactly the registers the
    reference computed, so all match only if the whole chain does; a
    wrong one is counted once, not again in every decision after it."""
    avail = np.asarray(avail0, np.float32)
    bad = 0
    for _, avg, ex, got in decisions:
        if not heft.same_decision(got, heft.heft_rt(avg, ex, avail)):
            bad += 1
        avail = np.asarray(got[4], np.float32)
    return bad


def misrouted(decisions, reqs) -> int:
    """Each decision maps every request that arrived since the last one,
    in due order (``run_continuous`` keeps them so); check that each was
    admitted where its decision sent it."""
    due_order = sorted(reqs, key=lambda r: (r.due, r.rid))
    bad, k = 0, 0
    for _, avg, _, got in decisions:
        pending = due_order[k:k + len(avg)]
        k += len(avg)
        order, assignment = got[0], got[1]
        for row, rep in zip(order, assignment):
            if pending[int(row)].replica != int(rep):
                bad += 1
    bad += len(due_order) - k       # requests no decision mapped
    return bad


def sample(window_reqs, seed: int, tokens: int) -> list:
    """The request with the most generated tokens, then others drawn from
    the seed until the sample holds ``tokens`` generated tokens."""
    done = [r for r in window_reqs if r.output is not None]
    if not done:
        return []
    first = max(done, key=lambda r: (r.new_tokens, -r.rid))
    rest = [r for r in done if r is not first]
    rng = np.random.default_rng(seed_sequence(seed).spawn(2)[1])
    rng.shuffle(rest)
    out, total = [first], first.new_tokens
    for r in rest:
        if total >= tokens:
            break
        out.append(r)
        total += r.new_tokens
    return out


def logit_gap(ref, params, hp, reqs, *, control: bool = False) -> float:
    """Widest gap over every generated token of ``reqs``."""
    worst = 0.0
    for r in reqs:
        g = ref.gaps(params, hp, len(r.prompt), r.output, control=control)
        worst = max(worst, float(g.max()))
    return worst


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in numbers)


def report(numbers: dict, limits: dict) -> dict:
    return {k: {"value": numbers[k], "limit": limits[k]} for k in numbers}
