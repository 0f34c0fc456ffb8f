"""Operations and bytes that a decode tick's work needs, by the algorithm.

Counted from the model's sizes (``reference.sizes``) and the positions of
the active lanes only:

* every weight matrix read once per tick (the embedding table only for the
  lanes' rows), with its matmul operations for each lane;
* attention over each lane's cached keys and values up to its position,
  read once, and the one new token's key and value written;
* nothing for padded lanes, the gathered dense view of the pages, scratch
  pages or the logits: those are what an implementation moves, not what
  the step needs, so a better kernel can only raise the share.

Elementwise work (norms, rotary embedding, softmax) is left out of the
operations; it is under a thousandth of the matmuls at these widths.
"""

from __future__ import annotations


def _bytes(dtype: str) -> int:
    return {"bfloat16": 2, "float32": 4}[dtype]


def matrix_params(hp: dict) -> int:
    """Weights multiplied per token: all layers' matrices and the head."""
    d, h, kv, hd, f, v = (hp[k] for k in ("d", "h", "kv", "hd", "f", "v"))
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return hp["layers"] * per_layer + d * v


def tick_work(hp: dict, positions) -> tuple[float, float]:
    """(operations, bytes) of one decode tick whose active lanes write
    their new token at ``positions`` (each lane attends to ``p + 1``
    keys: ``p`` cached and its own)."""
    d, h, kv, hd, L = (hp[k] for k in ("d", "h", "kv", "hd", "layers"))
    wb = _bytes(hp["dtype"])
    b = len(positions)
    keys = sum(int(p) + 1 for p in positions)
    cached = sum(int(p) for p in positions)
    flops = 2.0 * b * matrix_params(hp) + 4.0 * L * h * hd * keys
    weights = wb * matrix_params(hp) + 4 * (2 * L + 1) * d + wb * b * d
    kv_bytes = wb * 2 * L * kv * hd * (cached + b)
    return flops, float(weights + kv_bytes)


def least_time_s(flops: float, nbytes: float, peak: dict,
                 chips: int) -> tuple[float, str]:
    """The larger of the compute and the memory bound on ``chips`` chips,
    and which of the two it is."""
    t_c = flops / (chips * peak["bf16_flops_per_s"])
    t_m = nbytes / (chips * peak["hbm_bytes_per_s"])
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
