"""Serving engine: continuous batching with a HEFT_RT front-end scheduler.

Two layers:

* ``ServeEngine`` — a real decode loop (prefill + batched token-by-token
  decode with KV/state caches) for a single replica.  Optionally *mesh-
  backed*: give it a ``repro.dist`` mesh slice and its prefill/decode steps
  jit under the ``replica_pspecs`` layouts (params FSDP+TP, KV heads over
  ``model``, batch replicated) with the activation hint policy installed —
  the replica becomes an actual multi-device substrate instead of an
  abstract speed factor.
* ``HeftFrontEnd`` — maps dynamically arriving requests onto a fleet of
  replicas with HEFT_RT (the paper's scheduler as the admission layer; see
  sched_integration/serve_scheduler.py for the fleet-scale simulation).
  Heterogeneous fleets mix replica mesh shapes (1×1, 2×1, 2×2 slices of one
  device pool — ``repro.launch.mesh.slice_device_pool``); per-replica
  ``Exec_TID`` estimates come from the dry-run cost-model registry when the
  replica's (arch × mesh) cells are covered, host-scale roofline otherwise.

Public contracts:

* **Dense path** (`generate`, `start`/`step`) — per-request decode against a
  dense fixed-shape cache; the *bitwise oracle* every other path is tested
  against.  `reshard(mesh)` migrates a live replica (params + in-flight KV)
  token-identically; `snapshot_caches`/`restore_caches` are the chaos tier's
  kill-and-recover unit.
* **Paged path** (`start_paged` → `admit`/`decode_tick`/`finished_slots`/
  `retire`) — continuous batching through the block-paged KV pool in
  `serve/paging.py`: requests join/leave a running batch without retracing
  (power-of-two lane buckets), admission *reserves every page up front* so
  pool exhaustion refuses admission (``admit() -> None`` — callers queue,
  never drop), and each request's token stream is bit-identical to
  ``generate`` under ANY admission interleaving.
  `snapshot_pages`/`restore_pages` move one in-flight request between
  engines at page granularity.  Design note: docs/serving.md.
* **Front end** — `run_batch` (one HEFT_RT mapping event, whole-batch
  generate per replica) and `run_continuous` (per-tick admission: HEFT_RT
  maps arrivals to sticky per-replica FIFO queues, each tick drains queue
  heads into free paged slots).  Both return outputs in request order.
"""

from __future__ import annotations

import contextlib
import math
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import heft_rt_numpy
from repro.dist.hints import sharding_policy
from repro.dist.sharding import MeshAxes, named, replica_pspecs, reshard_tree
from repro.models.config import ModelConfig
from repro.models.model import decode_step, init_params_on, prefill_step
from repro.obs.metrics import Stopwatch
from repro.obs.trace import NULL_SPAN


def _host_scale_s(prompt_tokens, new_tokens):
    """The abstract-fleet service-time estimate (seconds, elementwise)."""
    return 1e-4 * prompt_tokens + 2e-3 * new_tokens


def _is_key(x) -> bool:
    return (isinstance(x, jax.Array)
            and jnp.issubdtype(x.dtype, jax.dtypes.prng_key))


def _span(tracer, name, **args):
    """Tracer span, or a no-op context when no tracer is attached."""
    if tracer is None:
        return contextlib.nullcontext()
    return tracer.span(name, **args)


@dataclass
class ServeEngine:
    """Single-replica engine: batched prefill + greedy decode.

    ``mesh``/``axes`` back the replica with a mesh slice: params are
    device_put to their FSDP+TP layout once, caches live sharded across the
    slice (KV heads over ``model``), and every step traces under
    ``jax.set_mesh`` + the replica's activation ``sharding_policy``.

    ``params`` may also be a PRNG key: the engine then initialises random
    weights from it directly in their final placement (the slice's
    layout, or the default device unmeshed).
    """

    cfg: ModelConfig
    params: dict
    max_len: int = 256
    mesh: object | None = None          # jax Mesh slice backing this replica
    axes: MeshAxes | None = None
    fsdp: bool = True
    tracer: object | None = None        # repro.obs.Tracer: step/reshard spans

    def __post_init__(self):
        self._paged = None              # PagedRuntime (start_paged)
        self._build()

    def _build(self):
        """(Re)place params and (re)build the compiled steps for the current
        mesh slice — the shared path of construction and live resharding."""
        if self.mesh is not None:
            ax = self.axes or MeshAxes()
            self.axes = ax
            specs = replica_pspecs(self.cfg, ax, fsdp=self.fsdp)
            p_sh = named(self.mesh, specs["params"])
            c_sh = named(self.mesh, specs["cache"])
            b_sh = named(self.mesh, specs["batch"])
            self._policy = dict(specs["policy"], __mesh__=self.mesh)
            self._cache_sh = c_sh
            with self._ctx():
                self.params = (init_params_on(self.params, self.cfg, p_sh)
                               if _is_key(self.params)
                               else reshard_tree(self.params, p_sh))
            self._decode = jax.jit(
                lambda p, c, t, pos: decode_step(p, c, t, pos, self.cfg),
                in_shardings=(p_sh, c_sh, b_sh, None),
                out_shardings=(None, c_sh), donate_argnums=(1,))
            self._prefill = jax.jit(
                lambda p, t: prefill_step(p, t, self.cfg, max_len=self.max_len),
                in_shardings=(p_sh, b_sh), out_shardings=(None, c_sh))
        else:
            self._policy = None
            self._cache_sh = None
            if _is_key(self.params):
                self.params = init_params_on(self.params, self.cfg)
            self._decode = jax.jit(
                lambda p, c, t, pos: decode_step(p, c, t, pos, self.cfg))
            self._prefill = jax.jit(
                lambda p, t: prefill_step(p, t, self.cfg, max_len=self.max_len))

    def reshard(self, mesh, axes: MeshAxes | None = None, caches=None):
        """Migrate this *live* replica to a new mesh slice, in memory.

        Params (and optionally a caller-held KV/state cache tree from an
        in-flight generation) are re-laid-out under the new slice's
        ``replica_pspecs`` via :func:`repro.dist.sharding.reshard_tree` — no
        checkpoint/disk round-trip — and the prefill/decode executables are
        rebuilt for the new mesh.  ``mesh=None`` migrates back to the
        unmeshed single-device engine.  Generation is bit-identical across
        the migration (the replica_pspecs layouts are value-preserving), so
        a fleet controller can move replicas between slice shapes mid-run
        without perturbing in-flight decodes.

        Returns the migrated cache tree (None when ``caches`` is None).
        """
        with _span(self.tracer, "engine.reshard",
                   to=str(tuple(mesh.devices.shape)) if mesh is not None
                   else "host",
                   with_caches=caches is not None):
            self.mesh = mesh
            if axes is not None:
                self.axes = axes
            if mesh is None:
                # Actually vacate the old slice: params must not stay
                # committed to devices the caller is about to re-carve for
                # other replicas.
                self.params = jax.tree.map(
                    lambda x: jnp.asarray(np.asarray(x)), self.params)
            self._build()
            if self._paged is not None:
                # Paged runtime: the page pool migrates as a unit (pages are
                # the live-migration granule) and the tick recompiles for
                # the new slice; in-flight slots keep decoding.
                self._paged.rebind()
            if caches is not None:
                if self._cache_sh is not None:
                    caches = reshard_tree(caches, self._cache_sh)
                else:
                    caches = jax.tree.map(
                        lambda x: jnp.asarray(np.asarray(x)), caches)
            return caches

    def snapshot_caches(self, caches):
        """Host-side snapshot of an in-flight KV/state cache tree.

        This is the chaos tier's recovery unit: taken at a committed decode
        step (between :meth:`step` calls), the snapshot outlives the
        replica's process — kill the engine mid-generation and
        :meth:`restore_caches` re-materializes the same step onto a spare
        slice, token-identical from the last committed token.
        """
        with _span(self.tracer, "engine.snapshot"):
            return jax.tree.map(lambda x: np.asarray(x), caches)

    def restore_caches(self, caches):
        """Re-materialize a :meth:`snapshot_caches` tree onto this replica's
        slice (its ``replica_pspecs`` cache layout via ``reshard_tree``;
        plain device residency unmeshed)."""
        with self._ctx(), _span(self.tracer, "engine.restore"):
            if self._cache_sh is not None:
                return reshard_tree(caches, self._cache_sh)
            return jax.tree.map(jnp.asarray, caches)

    @property
    def mesh_shape(self) -> tuple[int, ...] | None:
        return tuple(self.mesh.devices.shape) if self.mesh is not None else None

    def _ctx(self):
        """Mesh + hint-policy context for traces/transfers (identity unmeshed)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        ctx = contextlib.ExitStack()
        ctx.enter_context(jax.set_mesh(self.mesh))
        ctx.enter_context(sharding_policy(self._policy))
        return ctx

    def start(self, prompts: np.ndarray):
        """Prefill: (B, S0) prompts → (logits, caches).

        With :meth:`step`, the resumable half of :meth:`generate` — a caller
        can pause decoding, migrate the caches through :meth:`reshard`, and
        resume on the new mesh slice.
        """
        with self._ctx(), _span(self.tracer, "engine.prefill",
                                B=int(prompts.shape[0]),
                                S0=int(prompts.shape[1])):
            return self._prefill(self.params, jnp.asarray(prompts))

    def step(self, caches, tok, pos: int):
        """One decode step: (caches, (B, 1) tokens, position) → (logits,
        caches).  The cache tree is donated (pass the latest one)."""
        with self._ctx(), _span(self.tracer, "engine.decode_step", pos=pos):
            return self._decode(self.params, caches, jnp.asarray(tok),
                                jnp.int32(pos))

    def generate(self, prompts: np.ndarray, new_tokens: int,
                 greedy: bool = True, seed: int = 0):
        """prompts: (B, S0) int32 → (B, S0+new_tokens) generated ids."""
        B, S0 = prompts.shape
        tr = self.tracer
        with self._ctx():
            t0 = time.perf_counter()
            logits, caches = self._prefill(self.params, jnp.asarray(prompts))
            if tr is not None:
                tr.complete("engine.prefill", t0, time.perf_counter() - t0,
                            B=B, S0=S0)
            out = [jnp.asarray(prompts)]
            key = jax.random.key(seed)
            tok = None
            for i in range(new_tokens):
                if greedy:
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                else:
                    key, sub = jax.random.split(key)
                    tok = jax.random.categorical(sub, logits).astype(jnp.int32)
                out.append(tok[:, None])
                t0 = time.perf_counter()
                logits, caches = self._decode(self.params, caches, tok[:, None],
                                              jnp.int32(S0 + i))
                if tr is not None:
                    tr.complete("engine.decode_step", t0,
                                time.perf_counter() - t0, pos=S0 + i)
            return np.asarray(jnp.concatenate(out, axis=1))

    # -- continuous batching (block-paged KV pool; see serve/paging.py) -----

    def start_paged(self, *, max_batch: int = 8, page_size: int = 16,
                    num_pages: int | None = None):
        """Switch this replica to the in-flight decode API.

        Builds the device-resident page pool (``num_pages`` defaults to full
        occupancy ``max_batch * max_len/page_size``; set it lower to
        exercise admission-gating exhaustion) and the compiled
        gather→decode→scatter tick.  After this, drive the engine with
        :meth:`admit` / :meth:`decode_tick` / :meth:`retire`; the dense
        :meth:`generate` path stays available and is the bitwise oracle the
        paged path is tested against.  Returns the
        :class:`~repro.serve.paging.PagedRuntime` (also kept on the engine).
        """
        from repro.serve.paging import PagedRuntime

        self._paged = PagedRuntime(self, max_batch, page_size,
                                   num_pages=num_pages)
        return self._paged

    @property
    def paged(self):
        """The active PagedRuntime, or None before :meth:`start_paged`."""
        return self._paged

    def _require_paged(self):
        if self._paged is None:
            raise RuntimeError("call start_paged() before the in-flight API")
        return self._paged

    def admit(self, prompt: np.ndarray, new_tokens: int) -> int | None:
        """Prefill + join the running batch without stopping it.

        Reserves the request's full page budget up front; returns the slot
        id, or ``None`` when the pool lacks a slot/pages — callers queue
        rejected requests (the contract is queue-never-drop; see
        ``HeftFrontEnd.run_continuous``).  A traced call's ``engine.admit``
        span says whether it ``admitted`` (1) or was refused (0).
        """
        rt = self._require_paged()
        tr = self.tracer
        if tr is None:
            return rt.admit(prompt, new_tokens)
        with tr.span("engine.admit", S0=int(np.asarray(prompt).size),
                     new_tokens=new_tokens) as span:
            slot = rt.admit(prompt, new_tokens)
            span.set(admitted=int(slot is not None))
        return slot

    def decode_tick(self, sched=None):
        """One decode step for every in-flight slot → {slot: new token}.

        ``sched`` (optional): a staged HEFT_RT mapping event ``(avg,
        exec_times, fabric)`` for a fused-backend
        :class:`~repro.sched_integration.fabric.MappingFabric` — the
        decision runs *inside* the tick's compiled program against the
        fabric's device-resident registers, and the call returns
        ``(tokens, decision)`` instead (see ``PagedRuntime.decode_tick``
        and docs/scheduling.md).

        With a tracer attached, a tick that runs at least one lane records
        an ``engine.decode_tick`` span (``active``, ``fused``,
        ``pages_reserved``, ``pages_written``, ``attn``; on a meshed engine
        also ``chips``, ``lanes``, ``view_bytes`` and ``exchange_bytes``)
        around ``tick.stage``, ``tick.wait`` and ``tick.commit`` phases; an
        empty tick records nothing."""
        return self._require_paged().decode_tick(sched)

    def finished_slots(self) -> list[int]:
        """Slots whose generation completed and await :meth:`retire`."""
        return self._require_paged().finished_slots()

    def retire(self, slot: int) -> np.ndarray:
        """Free a finished slot's pages; returns its (S0+new_tokens,) ids."""
        return self._require_paged().retire(slot)

    def free_pages(self) -> int:
        """Pages currently available for admission."""
        return self._require_paged().pool.free_pages

    def snapshot_pages(self, slot: int) -> dict:
        """Page-granular snapshot of ONE in-flight request (the continuous-
        batching analogue of :meth:`snapshot_caches`: O(request), not
        O(pool)).  Restore with :meth:`restore_pages` on any paged engine."""
        with _span(self.tracer, "engine.snapshot_pages", slot=slot):
            return self._require_paged().snapshot_slot(slot)

    def restore_pages(self, snap: dict) -> int | None:
        """Re-admit a :meth:`snapshot_pages` request here; decoding resumes
        token-identically.  None when the pool is currently full."""
        with _span(self.tracer, "engine.restore_pages"):
            return self._require_paged().restore_slot(snap)


@dataclass
class ReplicaHandle:
    """One fleet slot: an engine plus its scheduling identity.

    ``speed`` scales the host-scale fallback estimate (legacy abstract
    fleets).  Mesh-backed replicas instead carry the cost-model key
    (``arch`` + ``mesh_shape``, auto-filled from the engine's mesh) and
    aggregate hardware rates, so the front-end's Exec_TID column can come
    from dry-run cost cells.
    """

    name: str
    engine: ServeEngine
    speed: float = 1.0             # relative throughput (heterogeneous fleet)
    avail_at: float = 0.0          # availability-time register (T_avail)
    processed: int = 0
    arch: str | None = None              # cost-model key
    mesh_shape: tuple[int, ...] | None = None
    compute_tflops: float | None = None  # aggregate effective rates
    hbm_gbps: float | None = None
    ici_gbps: float = 0.0

    def __post_init__(self):
        if self.mesh_shape is None:
            self.mesh_shape = self.engine.mesh_shape

    def sync_mesh_identity(self) -> None:
        """Re-derive the scheduling identity after ``engine.reshard``.

        The cost-model key follows the engine's new slice, and ``speed`` /
        aggregate rates rescale with the device count — without this, the
        front end keeps scheduling the migrated replica with the *old*
        slice's Exec_TID column.
        """
        old_n = math.prod(self.mesh_shape) if self.mesh_shape else 1
        self.mesh_shape = self.engine.mesh_shape
        new_n = math.prod(self.mesh_shape) if self.mesh_shape else 1
        if new_n != old_n:
            scale = new_n / old_n
            self.speed *= scale
            if self.compute_tflops:
                self.compute_tflops *= scale
            if self.hbm_gbps:
                self.hbm_gbps *= scale


class _Lifecycles:
    """``run_continuous``'s traced bookkeeping: one ``request`` span per
    request, the ``sched.*`` phases of each mapping event (numbered
    ``ev``), and one ``loop.idle`` phase per run of loop iterations with
    nothing in flight, queued or pending.  Built only when a tracer is
    attached; while entered, it also records ``host.gc``
    (:meth:`~repro.obs.trace.Tracer.watch_gc`)."""

    def __init__(self, tracer):
        self.tr = tracer
        self.ev = 0             # the mapping event being decided
        # req idx → [seen, decided, admitted, first token, ev, path,
        # replica], times on the perf_counter clock
        self.reqs: dict[int, list] = {}
        self.idle = None        # open loop.idle phase
        self.idle_n = 0
        self.gc = None          # the Tracer.watch_gc hook while entered

    def iteration(self, batch, pending, queues, replicas) -> None:
        now = time.perf_counter()
        for i in batch:
            self.reqs[i] = [now, now, now, now, -1, "", -1]
        busy = (batch or pending or any(queues)
                or any(r.engine.paged.slots for r in replicas))
        if not busy:
            if self.idle is None:
                self.idle = self.tr.begin("loop.idle")
                self.idle_n = 0
            self.idle_n += 1
        elif self.idle is not None:
            self.idle.end(iterations=self.idle_n)
            self.idle = None

    def phase(self, name: str, reqs: list[int], path: str):
        return self.tr.phase(name, ev=self.ev, n=len(reqs), path=path)

    def decided(self, reqs: list[int], path: str) -> None:
        now = time.perf_counter()
        for i in reqs:
            rec = self.reqs[i]
            rec[1], rec[4], rec[5] = now, self.ev, path
        self.ev += 1

    def admitted(self, i: int, replica: int, t0: float) -> None:
        rec = self.reqs[i]
        rec[2], rec[3], rec[6] = t0, time.perf_counter(), replica

    def retired(self, i: int, tokens: int) -> None:
        seen, decided, admitted, first, ev, path, replica = self.reqs.pop(i)
        self.tr.complete("request", seen, time.perf_counter() - seen,
                         rid=i, replica=replica, path=path, ev=ev,
                         tokens=tokens, decided_s=decided - seen,
                         admitted_s=admitted - seen,
                         first_token_s=first - seen)

    def __enter__(self):
        self.gc = self.tr.watch_gc().__enter__()
        return self

    def __exit__(self, *exc):
        if self.idle is not None:
            self.idle.end(iterations=self.idle_n)
            self.idle = None
        self.gc.__exit__(*exc)
        return False


@dataclass
class HeftFrontEnd:
    """HEFT_RT request→replica mapper over live engines.

    Mirrors the paper's runtime loop: each scheduling tick, the ready queue
    of requests is passed with per-replica exec-time estimates and T_avail
    registers to the HEFT_RT scheduler; commitments execute on the engines.

    ``fabric`` selects the mapping-event backend: ``None`` keeps the
    unbatched ``heft_rt_numpy`` oracle; a
    :class:`~repro.sched_integration.fabric.MappingFabric` routes events
    through the bucketed jit/Pallas dispatch pipeline (identical decisions,
    device-resident T_avail registers).

    ``cost_registry`` (a
    :class:`~repro.sched_integration.cost_model.CostModelRegistry`) supplies
    dry-run-derived Exec_TID columns for replicas whose (arch × mesh) cells
    it covers; uncovered replicas keep the host-scale estimate.
    """

    replicas: list[ReplicaHandle]
    fabric: object | None = None      # MappingFabric, optional
    cost_registry: object | None = None
    tracer: object | None = None      # repro.obs.Tracer: decision spans
    metrics: object | None = None     # repro.obs.MetricsRegistry
    unreachable: set = field(default_factory=set)   # chaos partition mask

    # -- dynamic handle registry (elastic fleet) ----------------------------

    def add_replica(self, handle: ReplicaHandle) -> None:
        """Join a replica mid-run.  With a fabric attached, the PE pool grows
        in place so the compiled dispatch keeps matching the fleet width.
        The resident registers are seeded at the joiner's ``avail_at`` for
        resident-register consumers; ``schedule()`` itself passes the
        handles' availability explicitly every event."""
        self.replicas.append(handle)
        if self.fabric is not None:
            self.fabric.grow(len(self.replicas), avail=handle.avail_at)
        self._sync_mask()

    def remove_replica(self, name: str) -> ReplicaHandle:
        """Retire a replica by name (in-flight work finishes; no new
        assignments).  The fabric shrinks keeping the survivors' registers."""
        idx = next((i for i, r in enumerate(self.replicas) if r.name == name),
                   None)
        if idx is None:
            raise KeyError(f"no replica named {name!r} in "
                           f"{[r.name for r in self.replicas]}")
        handle = self.replicas.pop(idx)
        if self.fabric is not None:
            self.fabric.shrink([i for i in range(len(self.replicas) + 1)
                                if i != idx])
        self.unreachable.discard(name)
        self._sync_mask()
        return handle

    def set_unreachable(self, names) -> None:
        """Chaos-tier partition mask: replicas in ``names`` stop receiving
        *new* work (their Exec_TID columns dispatch as ``+inf``, and an
        attached fabric's PE mask follows) while in-flight generations and
        committed ``T_avail`` registers stay intact for recovery.  Pass an
        empty iterable to clear.  Names not in the roster are ignored —
        a partition can outlive the replicas behind it."""
        self.unreachable = set(names)
        self._sync_mask()

    def _sync_mask(self) -> None:
        # Fabric resizes clear the lane mask (indices change meaning), so
        # every roster/mask change re-derives it from replica names.
        if self.fabric is None:
            return
        mask = np.array([r.name in self.unreachable for r in self.replicas],
                        dtype=bool)
        self.fabric.set_pe_mask(mask if mask.any() else None)

    def estimate_s(self, prompt_len: int, new_tokens: int,
                   replica: ReplicaHandle) -> float:
        return _host_scale_s(prompt_len, new_tokens) / replica.speed

    def exec_estimates(self, requests: list[tuple[np.ndarray, int]]
                       ) -> np.ndarray:
        """(n, P) Exec_TID matrix: cost-model columns where the registry
        covers a replica, host-scale roofline fallback elsewhere."""
        pf = np.array([len(pr) for pr, _ in requests], dtype=np.float64)
        dc = np.array([nt for _, nt in requests], dtype=np.float64)
        cols = []
        for r in self.replicas:
            if r.name in self.unreachable:
                cols.append(np.full(len(requests), np.inf))
                continue
            col = (self.cost_registry.column_s(r, pf, dc)
                   if self.cost_registry is not None else None)
            if col is None:
                col = _host_scale_s(pf, dc) / r.speed
            cols.append(col)
        return np.stack(cols, axis=1)

    def schedule(self, requests: list[tuple[np.ndarray, int]]):
        """requests: [(prompt, new_tokens)] → list of (req_idx, replica_idx)."""
        n, p = len(requests), len(self.replicas)
        if self.tracer is not None:
            self.tracer.counter("frontend.queue_depth", depth=n)
        t0 = time.perf_counter()
        ex = self.exec_estimates(requests)
        avg = ex.mean(axis=1)
        avail = np.array([r.avail_at for r in self.replicas])
        if self.fabric is not None:
            order, assignment, start, finish, new_avail = self.fabric.map_event(
                avg, ex, avail, update=False)
        else:
            order, assignment, start, finish, new_avail = heft_rt_numpy(
                avg, ex, avail)
        dt = time.perf_counter() - t0
        if self.tracer is not None:
            self.tracer.complete("frontend.schedule", t0, dt, n=n, p=p)
        if self.metrics is not None:
            # Per-decision scheduler latency: one measured batched event
            # amortized over its n decisions (weight n keeps counts honest).
            self.metrics.histogram("frontend.decision_s").record(
                dt / max(n, 1), n=max(n, 1))
        # One host materialization for the whole register file, not one
        # blocking float() per replica (host-sync-in-hot-path design rule).
        new_avail = np.asarray(new_avail)
        for i, r in enumerate(self.replicas):
            r.avail_at = float(new_avail[i])
        return [(int(order[i]), int(assignment[i])) for i in range(n)]

    # -- fused-scheduler helpers (docs/scheduling.md) -----------------------

    def _fused_enabled(self, fused: bool | None) -> bool:
        """Resolve ``run_continuous``'s ``fused`` knob: None follows the
        attached fabric's backend; True demands a fused-backend fabric."""
        is_fused = (self.fabric is not None
                    and getattr(self.fabric, "backend", None) == "fused")
        if fused is None:
            return is_fused
        if fused and not is_fused:
            raise ValueError(
                "fused=True requires a MappingFabric(backend='fused') "
                f"front-end fabric, got "
                f"{getattr(self.fabric, 'backend', None)!r}")
        return bool(fused)

    def _stage_event(self, requests: list[tuple[np.ndarray, int]]):
        """(avg, exec_times) for one mapping event — the operand half of
        :meth:`schedule`, reused by the fused tick path."""
        ex = self.exec_estimates(requests)
        return ex.mean(axis=1), ex

    def _adopt_decision(self, n: int, decision):
        """Turn a mapping-event 5-tuple into a plan, mirroring the fabric's
        resident ``new_avail`` registers into the replica handles (the
        fused-path twin of :meth:`schedule`'s bookkeeping)."""
        order, assignment, _, _, new_avail = decision
        new_avail = np.asarray(new_avail)
        for i, r in enumerate(self.replicas):
            r.avail_at = float(new_avail[i])
        if self.tracer is not None:
            self.tracer.counter("frontend.queue_depth", depth=n)
        return [(int(order[i]), int(assignment[i])) for i in range(n)]

    def run_batch(self, requests: list[tuple[np.ndarray, int]]):
        """Schedule + execute, returning (outputs, per-replica counts)."""
        plan = self.schedule(requests)
        outputs: dict[int, np.ndarray] = {}
        gen_hist = (self.metrics.histogram("engine.generate_s")
                    if self.metrics is not None else None)
        for req_idx, rep_idx in plan:
            prompt, new_tokens = requests[req_idx]
            rep = self.replicas[rep_idx]
            with Stopwatch(gen_hist) as sw:
                outputs[req_idx] = rep.engine.generate(prompt[None, :],
                                                       new_tokens)
            if self.tracer is not None:
                self.tracer.complete("frontend.generate", sw.start_s,
                                     sw.elapsed_s, replica=rep.name,
                                     new_tokens=new_tokens)
            rep.processed += 1
        return [outputs[i] for i in range(len(requests))], \
            {r.name: r.processed for r in self.replicas}

    def run_continuous(self, requests: list[tuple[np.ndarray, int]], *,
                       arrival_ticks: list[int] | None = None,
                       max_batch: int = 8, page_size: int = 16,
                       num_pages: int | None = None,
                       fused: bool | None = None):
        """Continuous batching: the admission tick the paper's scheduler
        needs to pay off on dynamic arrivals.

        Each tick, requests that have arrived are mapped to replicas with
        HEFT_RT (:meth:`schedule` — one sticky decision per request), each
        replica drains its mapped queue head-first into free batch slots
        (``admit``; a refusal re-queues, FIFO order preserved — pool
        exhaustion *queues*, never drops), then every replica runs one
        ``decode_tick`` and retires finished slots.  Requests join and leave
        the running batch without stopping it, and each request's tokens are
        bit-identical to ``engine.generate`` run alone — under any
        interleaving (the paged-oracle contract, property-tested).

        ``arrival_ticks[i]`` (default all 0) is the decode tick at which
        request ``i`` becomes visible — the open-loop workload hook the
        paged-serve benchmark drives.

        ``fused`` selects the zero-host-round-trip scheduling fast path
        (default: on exactly when the attached fabric is
        ``backend="fused"``): arrivals' HEFT_RT decisions run *inside* a
        replica's decode-tick program against the fabric's device-resident
        registers, riding the token transfer the tick already makes
        (docs/scheduling.md).  Mapped requests join their queues one tick
        later than the host path — a pipeline delay, not a drop; when no
        replica has active slots to ride (cold start, idle fleet) the
        decision takes the host path against the same resident registers.
        Token streams stay bit-identical to ``generate`` either way.

        Returns ``(outputs, stats)``: outputs in request order, and stats
        with ``ticks``, per-replica ``processed``, the pools' cumulative
        ``allocated`` / ``freed`` page counters (equal at drain), and the
        ``fused_decisions`` / ``host_decisions`` split.

        With a tracer attached (``self.tracer``; engines carry their own),
        the loop records a ``request`` span per request (``rid``,
        ``replica``, ``path``, ``ev``, ``tokens`` and the offsets
        ``decided_s`` / ``admitted_s`` / ``first_token_s`` from when the
        loop first saw it due to its retirement), ``sched.stage`` /
        ``sched.decide`` / ``sched.adopt`` phases per mapping event (``ev``,
        ``n``, ``path``), one ``loop.idle`` phase per run of iterations with
        nothing in flight, queued or pending (``iterations``), and a
        ``host.gc`` phase per garbage collection while it runs (docs/knobs.md
        "Observability").  Untraced, each site costs one ``is None`` check.
        """
        arrivals = arrival_ticks or [0] * len(requests)
        if len(arrivals) != len(requests):
            raise ValueError("arrival_ticks must match requests")
        fused = self._fused_enabled(fused)
        fused_decisions = host_decisions = 0
        if fused:
            # The fabric's register file becomes the source of truth for
            # T_avail during the run; seed it from the handles once, then
            # every decision (fused tick or idle-time host fallback) updates
            # the resident registers and mirrors them back.
            self.fabric.reset(np.array([r.avail_at for r in self.replicas],
                                       dtype=np.float64))
        for r in self.replicas:
            if r.engine.paged is None:
                r.engine.start_paged(max_batch=max_batch,
                                     page_size=page_size,
                                     num_pages=num_pages)
            pool = r.engine.paged.pool
            for prompt, nt in requests:
                need = pool.pages_needed(len(prompt) + nt)
                if need > pool.num_pages:
                    raise ValueError(
                        f"request needs {need} pages but the pool holds "
                        f"{pool.num_pages} — it could never be admitted")
        order = sorted(range(len(requests)), key=lambda i: (arrivals[i], i))
        queues: list[list[int]] = [[] for _ in self.replicas]   # req idx FIFO
        slot_of: dict[tuple[int, int], int] = {}    # (rep, slot) → req idx
        outputs: dict[int, np.ndarray] = {}
        pending: list[int] = []     # fused path: arrived, not yet mapped
        tick = 0
        next_arrival = 0
        tr = self.tracer
        life = _Lifecycles(tr) if tr is not None else None
        with NULL_SPAN if life is None else life:
            while len(outputs) < len(requests):
                # 1. HEFT_RT-map the newly arrived requests (sticky decisions).
                batch = []
                while (next_arrival < len(order)
                       and arrivals[order[next_arrival]] <= tick):
                    batch.append(order[next_arrival])
                    next_arrival += 1
                if life is not None:
                    life.iteration(batch, pending, queues, self.replicas)
                carrier = None
                if not fused:
                    if batch:
                        with (NULL_SPAN if life is None else
                              life.phase("sched.decide", batch, "host")):
                            plan = self.schedule([requests[i] for i in batch])
                        for req_i, rep_i in plan:
                            queues[rep_i].append(batch[req_i])
                        if life is not None:
                            life.decided(batch, "host")
                else:
                    pending.extend(batch)
                    if pending:
                        # The decision rides the first replica that will run
                        # a decode tick this round; with nothing in flight
                        # there is no tick to ride — take the host path now
                        # (against the same resident registers) so this tick
                        # admits.
                        carrier = next(
                            (i for i, r in enumerate(self.replicas)
                             if r.engine.paged is not None
                             and r.engine.paged.active_slots()), None)
                        if carrier is None:
                            with (NULL_SPAN if life is None else
                                  life.phase("sched.stage", pending, "host")):
                                avg, ex = self._stage_event(
                                    [requests[i] for i in pending])
                            with (NULL_SPAN if life is None else
                                  life.phase("sched.decide", pending, "host")):
                                decision = self.fabric.map_event(avg, ex)
                            with (NULL_SPAN if life is None else
                                  life.phase("sched.adopt", pending, "host")):
                                plan = self._adopt_decision(len(pending),
                                                            decision)
                            host_decisions += len(pending)
                            for req_i, rep_i in plan:
                                queues[rep_i].append(pending[req_i])
                            if life is not None:
                                life.decided(pending, "host")
                            pending = []
                # 2. Admission tick: drain each mapped queue into free slots.
                for rep_i, r in enumerate(self.replicas):
                    while queues[rep_i]:
                        idx = queues[rep_i][0]
                        prompt, nt = requests[idx]
                        t0 = time.perf_counter() if life is not None else 0
                        slot = r.engine.admit(prompt, nt)
                        if slot is None:       # exhausted: stays queued (FIFO)
                            break
                        queues[rep_i].pop(0)
                        slot_of[(rep_i, slot)] = idx
                        if life is not None:
                            life.admitted(idx, rep_i, t0)
                # 3. Decode tick + retire finished slots.  On the fused path
                # the carrier's tick also computes the pending arrivals'
                # mapping inside its compiled program; the mapped requests
                # reach their queues for the NEXT admission tick (a one-tick
                # pipeline delay — the steady-state cost of zero host
                # round-trips).
                for rep_i, r in enumerate(self.replicas):
                    if fused and pending and rep_i == carrier:
                        with (NULL_SPAN if life is None else
                              life.phase("sched.stage", pending, "fused")):
                            avg, ex = self._stage_event(
                                [requests[i] for i in pending])
                        _, decision = r.engine.decode_tick(
                            (avg, ex, self.fabric))
                        with (NULL_SPAN if life is None else
                              life.phase("sched.adopt", pending, "fused")):
                            plan = self._adopt_decision(len(pending), decision)
                        fused_decisions += len(pending)
                        for req_i, rep_to in plan:
                            queues[rep_to].append(pending[req_i])
                        if life is not None:
                            life.decided(pending, "fused")
                        pending = []
                    else:
                        r.engine.decode_tick()
                    for slot in r.engine.finished_slots():
                        idx = slot_of.pop((rep_i, slot))
                        outputs[idx] = r.engine.retire(slot)
                        r.processed += 1
                        if life is not None:
                            life.retired(idx, requests[idx][1])
                tick += 1
        stats = {
            "ticks": tick,
            "processed": {r.name: r.processed for r in self.replicas},
            "allocated": sum(r.engine.paged.pool.allocated
                             for r in self.replicas),
            "freed": sum(r.engine.paged.pool.freed for r in self.replicas),
            "fused_decisions": fused_decisions,
            "host_decisions": host_decisions,
        }
        return [outputs[i] for i in range(len(requests))], stats


def mesh_backed_fleet(cfg: ModelConfig, params, mesh_shapes,
                      *, max_len: int = 128, arch: str | None = None,
                      axes: MeshAxes | None = None, devices=None,
                      chip_tflops: float = 1.0, chip_hbm_gbps: float = 1.0,
                      ici_gbps: float = 0.0, return_spare: bool = False):
    """Carve the device pool into mesh slices and build one engine each.

    The heterogeneous serve fleet in one call: ``mesh_shapes`` like
    ``[(1, 1), (2, 1), (2, 2)]`` produce replicas of mixed parallelism whose
    aggregate rates (and HEFT_RT speed fallback) scale with slice size.
    ``return_spare=True`` additionally returns the pool's uncarved devices
    (``slice_device_pool``'s remainder) — the spare budget elastic resize
    events re-carve later.  ``params`` is a weight tree, or a PRNG key from
    which each slice initialises its weights in its own layout.
    """
    from repro.launch.mesh import slice_device_pool

    ax = axes or MeshAxes()
    meshes, spare = slice_device_pool(mesh_shapes, (ax.data, ax.model),
                                      devices=devices, return_remainder=True)
    fleet = []
    for i, mesh in enumerate(meshes):
        shape = tuple(mesh.devices.shape)
        n = math.prod(shape)
        eng = ServeEngine(cfg, params, max_len=max_len, mesh=mesh, axes=ax)
        fleet.append(ReplicaHandle(
            f"{cfg.name}@{'x'.join(map(str, shape))}#{i}", eng,
            speed=float(n), arch=arch or cfg.name,
            compute_tflops=n * chip_tflops, hbm_gbps=n * chip_hbm_gbps,
            ici_gbps=ici_gbps))
    if return_spare:
        return fleet, spare
    return fleet
