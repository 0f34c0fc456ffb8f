"""Beyond-paper: pod-compressed training step — wire bytes + step time.

Exact f32 vs int8 error-feedback cross-pod gradient reduction on a (2, 2)
``(pod, data)`` mesh (fake CPU devices, spawned in a subprocess so the fixed
device count of this process is untouched).  Two row families:

* ``..._wire_*`` [B] — deterministic per-step cross-pod payload model:
  f32 sends 4 bytes/element; the int8 collective sends 1 byte/element plus
  one f32 absmax per leaf (the shared-grid ``pmax``).  This is the *logical*
  wire format — the CPU emulation in ``dist/compression.py`` materializes the
  int32 accumulator, a real multi-pod deployment sums int8 payloads with
  int32 accumulation on the wire.
* ``..._step_*`` [ms] — measured steady-state train-step wall time through
  the full residual-carrying ``make_train_step`` pod path (vmap-over-pods
  gradients + shard_map manual reduce), compilation excluded by warmup.

The CI gate (run.py --check) tracks both: a wire-bytes rise means the
compression silently widened; a step-time blowup means the pod path started
recompiling or falling off the fast path.
"""

import json

from tests._subproc import run_sub

_SUB = """
import json, time
import jax, jax.numpy as jnp
from repro.models import ModelConfig
from repro.models.model import init_params
from repro.optim import AdamWConfig, init_opt_state
from repro.data import DataConfig, TokenPipeline
from repro.launch.mesh import make_mesh
from repro.train import make_train_step

cfg = ModelConfig(name='bench', num_layers=4, d_model=64, num_heads=4,
                  num_kv_heads=2, d_ff=256, vocab_size=256,
                  param_dtype='float32', compute_dtype='float32')
ocfg = AdamWConfig(learning_rate=1e-3)
params = init_params(jax.random.key(0), cfg)
opt = init_opt_state(params, ocfg)
batch = {k: jnp.asarray(v) for k, v in TokenPipeline(
    DataConfig(vocab_size=256, seq_len=64, global_batch=8)
).batch_at(0).items()}

from repro.dist.compression import (EXACT_BYTES_PER_ELEM, WIRE_BYTES_PER_ELEM,
                                    WIRE_SCALE_BYTES_PER_LEAF)

leaves = jax.tree.leaves(params)
n_elems = sum(l.size for l in leaves)
n_leaves = len(leaves)

mesh = make_mesh((2, 2), ('pod', 'data'))
out = {'n_elems': int(n_elems), 'n_leaves': int(n_leaves),
       'params_m': float(n_elems / 1e6),
       'wire_exact': int(EXACT_BYTES_PER_ELEM * n_elems),
       'wire_int8': int(WIRE_BYTES_PER_ELEM * n_elems
                        + WIRE_SCALE_BYTES_PER_LEAF * n_leaves)}

def timed(step, state):
    p, o, r = state
    p, o, r, _ = step(p, o, r, batch)          # warmup/compile
    p, o, r, m = step(p, o, r, batch)
    jax.block_until_ready(m['loss'])
    times = []
    for _ in range(7):
        t0 = time.perf_counter()
        p, o, r, m = step(p, o, r, batch)
        jax.block_until_ready(m['loss'])
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e3        # median ms

with jax.set_mesh(mesh):
    for name, compress in (('exact', False), ('int8', True)):
        step = jax.jit(make_train_step(cfg, ocfg, pod_axis='pod',
                                       compress_pods=compress, mesh=mesh),
                       donate_argnums=(0, 1, 2))
        state = (init_params(jax.random.key(0), cfg),
                 init_opt_state(params, ocfg), None)
        out[f'step_{name}_ms'] = timed(step, state)

print(json.dumps(out))
"""


def _measure() -> dict:
    # same fake-device subprocess runner the multi-device tests use
    out = run_sub(_SUB, devices=4)
    return json.loads(out.strip().splitlines()[-1])


def run():
    m = _measure()
    # per-pod per-step cross-pod payload (the slow-link traffic), derived
    # from dist.compression's wire-format constants inside the subprocess
    wire_exact = m["wire_exact"]
    wire_int8 = m["wire_int8"]
    rows = [
        ("train_compress_wire_exact", float(wire_exact), "B",
         f"f32 all-reduce payload;elems={m['n_elems']}"),
        ("train_compress_wire_int8", float(wire_int8), "B",
         f"int8 payload + f32 amax/leaf;leaves={m['n_leaves']}"),
        ("train_compress_wire_ratio", wire_exact / wire_int8, "x",
         "exact/int8 wire bytes;acceptance>=3.5"),
        ("train_compress_step_exact", m["step_exact_ms"], "ms",
         f"(2,2) mesh pod step;params={m['params_m']:.2f}M"),
        ("train_compress_step_int8", m["step_int8_ms"], "ms",
         "int8 error-feedback reduce, residual carried"),
        ("train_compress_int8_overhead", m["step_int8_ms"] / m["step_exact_ms"],
         "x", "int8 step time / exact step time"),
    ]
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run())
