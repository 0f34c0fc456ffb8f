"""Plain reference for llama-architecture decoders (deepseek-llm-7b).

The published model (arXiv:2401.02954; huggingface.co/deepseek-ai/
deepseek-llm-7b-base, ``LlamaForCausalLM``): token embedding, then per
layer ``x += attn(rmsnorm(x))`` and ``x += ffn(rmsnorm(x))`` with
multi-head attention under rotary position embedding (the rotate-half
convention, ``rope_theta``), a SwiGLU feed-forward block
(``down(silu(gate(h)) * up(h))``), a final RMSNorm and an untied output
head.  Computed here in float32 at the highest matmul precision, with no
cache, batching or kernels, over one whole sequence at a time.

Nothing of the program under test is imported.  This file also makes the
weights (random, from the seed) in the tree layout the serving program
takes, so the program and the reference start from the same numbers and
neither takes anything the other made:

    {"embed": (V, D), "lm_head": (D, V), "final_norm": (D,), "first": [],
     "stages": {"sub0": {"norm_1": (L, D), "norm_2": (L, D),
                         "mixer": {"wq": (L, D, H*hd), "wk": (L, D, KV*hd),
                                   "wv": (L, D, KV*hd), "wo": (L, H*hd, D)},
                         "ffn": {"w_gate": (L, D, F), "w_up": (L, D, F),
                                 "w_down": (L, F, D)}}}}

Matrices are stored in the configuration's dtype, norm gains in float32
as their offset from 1 (a gain of ``1 + w``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def sizes(config: dict) -> dict:
    """The sizes the reference reads from a configuration file."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    return {
        "d": d, "h": h, "kv": config["num_key_value_heads"], "hd": d // h,
        "f": config["intermediate_size"], "v": config["vocab_size"],
        "layers": config["num_hidden_layers"],
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "dtype": config["torch_dtype"],
    }


def weight_shapes(hp: dict) -> dict:
    """ShapeDtypeStruct tree of the weights (the program's layout)."""
    d, h, kv, hd, f, v, L = (hp[k] for k in
                             ("d", "h", "kv", "hd", "f", "v", "layers"))
    dt = DTYPES[hp["dtype"]]

    def s(*shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype)

    f32 = jnp.float32
    return {
        "embed": s(v, d), "lm_head": s(d, v), "final_norm": s(d, dtype=f32),
        "first": [],
        "stages": {"sub0": {
            "norm_1": s(L, d, dtype=f32), "norm_2": s(L, d, dtype=f32),
            "mixer": {"wq": s(L, d, h * hd), "wk": s(L, d, kv * hd),
                      "wv": s(L, d, kv * hd), "wo": s(L, h * hd, d)},
            "ffn": {"w_gate": s(L, d, f), "w_up": s(L, d, f),
                    "w_down": s(L, f, d)}}},
    }


def make_weights(key, hp: dict, shardings=None) -> dict:
    """Random weights from ``key`` in one compiled program, born on the
    device (in ``shardings`` where given) in the type they are served in.

    Matrices are normal with standard deviation ``1/sqrt(fan_in)``, the
    embedding ``0.02`` (the published ``initializer_range``), norm gains
    ``1 + N(0, 0.05)``."""
    shapes = weight_shapes(hp)
    leaves, tdef = jax.tree.flatten(shapes)

    def gen(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, s) in zip(keys, jax.tree_util.tree_flatten_with_path(
                shapes)[0]):
            name = str(getattr(path[-1], "key", path[-1]))
            if name.startswith("norm") or name == "final_norm":
                x = 0.05 * jax.random.normal(k, s.shape, jnp.float32)
            elif name == "embed":
                x = 0.02 * jax.random.normal(k, s.shape, jnp.float32)
            else:
                fan_in = s.shape[-2]
                x = jax.random.normal(k, s.shape, jnp.float32) / np.sqrt(
                    fan_in)
            out.append(x.astype(s.dtype))
        return tdef.unflatten(out)

    return jax.jit(gen, out_shardings=shardings)(key)


# ---------------------------------------------------------------------------
# forward pass, float32
# ---------------------------------------------------------------------------

def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, theta):
    """x: (S, n, hd); rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv              # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _identity(x):
    return x


def fp8_e4m3(x):
    """Quantize-dequantize to float8 e4m3 with one scale per row of the
    last axis (per token for activations, per output column for weights
    once transposed by the caller): what a W8A8 fp8 serving path keeps."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16_round(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _linear(x, w, quant):
    """x (S, i) @ w (i, o) in float32; ``quant`` rounds both operands
    (per token for x, per output column for w)."""
    w = w.astype(jnp.float32)
    if quant is not _identity:
        x, w = quant(x), quant(w.T).T
    return jnp.matmul(x, w, precision=HIGHEST)


def _forward(params, tokens, hp, quant):
    """tokens (S,) -> logits (S, V) in float32."""
    S = tokens.shape[0]
    h, kv, hd = hp["h"], hp["kv"], hp["hd"]
    eps, theta = hp["eps"], hp["theta"]
    pos = jnp.arange(S)
    causal = jnp.tril(jnp.ones((S, S), bool))
    x = params["embed"][tokens].astype(jnp.float32)
    st = params["stages"]["sub0"]

    def layer(x, lw):
        a = _rms_norm(x, lw["norm_1"], eps)
        q = _linear(a, lw["mixer"]["wq"], quant).reshape(S, h, hd)
        k = _linear(a, lw["mixer"]["wk"], quant).reshape(S, kv, hd)
        v = _linear(a, lw["mixer"]["wv"], quant).reshape(S, kv, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
        s = jnp.einsum("qnd,knd->nqk", q, k, precision=HIGHEST) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("nqk,knd->qnd", p, v, precision=HIGHEST)
        x = x + _linear(o.reshape(S, h * hd), lw["mixer"]["wo"], quant)
        b = _rms_norm(x, lw["norm_2"], eps)
        g = _linear(b, lw["ffn"]["w_gate"], quant)
        u = _linear(b, lw["ffn"]["w_up"], quant)
        x = x + _linear(jax.nn.silu(g) * u, lw["ffn"]["w_down"], quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, st)
    x = _rms_norm(x, params["final_norm"], eps)
    return _linear(x, params["lm_head"], quant)


def _reference_rows(params, tokens, targets, hp_items):
    """Per position: the reference's top logit and the logit of
    ``targets``."""
    hp = dict(hp_items)
    logits = _forward(params, tokens, hp, _identity)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return logits.max(axis=1), picked


def _control_rows(params, tokens, hp_items, quant_name):
    """Per position: the reference's top logit and its logit of the token
    the lower-precision control puts first."""
    hp = dict(hp_items)
    quant = {"fp8_e4m3": fp8_e4m3, "bf16": bf16_round}[quant_name]
    ref = _forward(params, tokens, hp, _identity)
    ctl = _forward(params, tokens, hp, quant)
    first = jnp.argmax(ctl, axis=1)
    return ref.max(axis=1), jnp.take_along_axis(ref, first[:, None], 1)[:, 0]


_reference_rows_jit = jax.jit(_reference_rows, static_argnums=3)
_control_rows_jit = jax.jit(_control_rows, static_argnums=(2, 3))


def control_precision(hp: dict) -> str:
    """The nearest precision below the configuration's: fp8 (e4m3, scaled
    per token and per output column) under bfloat16, bfloat16 under
    float32."""
    return {"bfloat16": "fp8_e4m3", "float32": "bf16"}[hp["dtype"]]


def _padded(seq: np.ndarray, bucket: int) -> np.ndarray:
    n = -(-len(seq) // bucket) * bucket
    out = np.zeros(n, np.int32)
    out[:len(seq)] = seq
    return out


def gaps(params, hp: dict, prompt_len: int, served: np.ndarray, *,
         control: bool = False, bucket: int = 256) -> np.ndarray:
    """How far below the reference's best logit each served token lies.

    ``served`` is the prompt followed by the generated tokens.  Row ``i``
    of the forward pass over ``served[:-1]`` predicts ``served[i + 1]``;
    the rows that predicted generated tokens are ``prompt_len - 1`` on.
    With ``control``, the gap is that of the token the lower-precision
    control puts first at each of those rows instead.  Sequences are
    padded to a multiple of ``bucket`` (causal, so padding is not seen).
    """
    n = len(served) - 1
    tokens = jnp.asarray(_padded(served[:-1], bucket))
    items = tuple(sorted(hp.items()))
    if control:
        top, pick = _control_rows_jit(params, tokens, items,
                                      control_precision(hp))
    else:
        targets = jnp.asarray(_padded(served[1:], bucket))
        top, pick = _reference_rows_jit(params, tokens, targets, items)
    top, pick = np.asarray(top)[:n], np.asarray(pick)[:n]
    return (top - pick)[prompt_len - 1:]
