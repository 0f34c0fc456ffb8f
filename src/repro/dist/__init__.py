"""repro.dist — the sharded execution substrate under the HEFT scheduler.

The serving/training north-star treats heterogeneous model replicas as the
paper's "PEs"; this package is what makes one replica an actual multi-device
substrate.  Three layers:

* :mod:`repro.dist.sharding` — **mesh sharding rules**: PartitionSpec trees
  for params / optimizer moments / KV-state caches, plus the activation hint
  policy the model forward consumes.
* :mod:`repro.dist.hints` — the **hint plumbing**: a ``sharding_policy``
  context installs a name → PartitionSpec mapping; ``shard_hint(x, name)``
  sites inside the model blocks (attention heads, FFN hidden, Mamba inner,
  MoE group/row layouts, layer boundaries) turn into
  ``with_sharding_constraint`` only when a policy is active — without one
  they are exact identities, so unit tests and smoke runs are unaffected.
* :mod:`repro.dist.compression` — **pod-level collectives**: ``psum_mean``
  and the int8 + error-feedback ``compressed_psum_mean`` used for cross-pod
  gradient reduction over the slow inter-pod links, plus the residual
  lifecycle helpers ``init_residual`` / ``reshard_residual`` (the residual is
  first-class train-step state, stacked per pod and checkpointed — see the
  contract in that module's docstring).

Axis conventions (used by every PartitionSpec this package emits)
-----------------------------------------------------------------
``MeshAxes`` names three logical mesh axes:

* ``pod``   — outermost data parallelism across pods (slow links).  Params
  and optimizer state are *replicated* over ``pod``; gradients cross it via
  the (optionally compressed) pod collectives.  ``None`` on single-pod
  meshes.
* ``data``  — fast data parallelism *and* the FSDP/ZeRO-3 axis: weight
  matrices shard their d_model-sized dim over ``data`` (``fsdp=True``) and
  are all-gathered transiently per layer.
* ``model`` — tensor parallelism: attention heads, FFN hidden dim, Mamba
  d_inner, MoE experts, and the vocab dim of embed/lm_head shard over
  ``model``.

Batch-like leading dims shard over ``(pod, data)`` when a pod axis exists,
else over ``data``.  MoE dispatch groups shard over *all* of
``(pod, data, model)`` so the (B, S, D) → (G, T_l, D) regroup splits at
existing shard boundaries and moves zero bytes.
"""

from __future__ import annotations

from repro.dist.compression import (
    compressed_psum_mean,
    init_residual,
    psum_mean,
    reshard_residual,
)
from repro.dist.hints import current_policy, shard_hint, sharding_policy
from repro.dist.sharding import (
    MeshAxes,
    activation_hint_policy,
    batch_pspec,
    cache_pspecs,
    named,
    opt_pspecs,
    param_pspecs,
    replica_pspecs,
    reshard_tree,
)

__all__ = [
    "MeshAxes", "activation_hint_policy", "batch_pspec", "cache_pspecs",
    "compressed_psum_mean", "current_policy", "init_residual", "named",
    "opt_pspecs", "param_pspecs", "psum_mean", "replica_pspecs",
    "reshard_residual", "reshard_tree", "shard_hint", "sharding_policy",
]
