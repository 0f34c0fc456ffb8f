"""MappingFabric — batched, device-resident HEFT_RT dispatch pipeline.

The paper's core observation is that once tasks arrive dynamically, the
*scheduler's own latency* — not schedule quality — gates throughput, which is
why HEFT_RT moves into the FPGA fabric (9.144 ns/decision).  This module is
the TPU-side analogue for the serve/runtime layers: instead of one host
round-trip per mapping event (build a Python exec matrix, call
``heft_rt_numpy``, scatter the result), mapping events are *batched through
the fabric*:

* **Bucketed shapes.**  Ready queues are padded to power-of-two M-buckets
  (``bucket_size``) so the persistent jitted dispatch compiles O(log D_max)
  variants instead of one per queue length.  The PE axis gets the same
  treatment (``p_bucket``): P is *state*, not a constant — ``grow`` /
  ``shrink`` / ``remap`` resize the pool mid-stream carrying committed
  ``T_avail`` bit-exact, and resizes inside a P bucket reuse every compiled
  variant.
* **Device-resident availability registers.**  The jitted dispatch is built
  with ``donate_argnums`` on ``T_avail``, so the availability registers live
  on device across mapping events (the paper's PE-handler register file) and
  the event stream never bounces them through host memory.
* **Selectable backend.**  ``backend="jit"`` runs :func:`repro.core.heft_rt`
  (vmapped for batches); ``backend="pallas"`` runs the fused overlay kernel
  :func:`repro.kernels.heft_rt_hw` (compiled on TPU/GPU, interpret-mode
  fallback elsewhere — logged once and visible via
  :attr:`MappingFabric.backend_effective`); ``backend="fused"`` keeps the
  PE mask device-resident too and exposes its registers to the paged decode
  tick (see :meth:`MappingFabric.tick_decision_inputs`), so the HEFT_RT
  decision can run *inside* the serving tick's compiled program with zero
  host scheduling round-trips (docs/scheduling.md); ``backend="numpy"`` is
  the oracle-exact host fast path used by the discrete-event simulators,
  where events are tiny and sequential.
* **Vectorized roofline front-end.**  :func:`service_time_matrix` computes
  the full (N, P) exec-time matrix in one vectorized op, replacing the
  per-request Python row loop (and unbounded per-rid cache) in the serving
  simulator.

Decision fidelity: all backends make mapping decisions *slot-for-slot
identical* to the :func:`repro.core.heft_rt_numpy` oracle (the repo's Fig. 3
claim) provided exec/avg values are exactly representable in float32 for the
device backends (the numpy backend is exact in float64).  Exec times must lie
in ``[0, +inf]``; an all-``inf`` row marks a task no PE supports (assignment
-1).  ``avg`` entries may be NaN (e.g. ``nanmean`` of an all-inf row): like
the oracle's ``argsort``, NaN-keyed tasks sort behind every finite key, and
always ahead of padding slots.
"""

from __future__ import annotations

import math
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.heft_rt import ScheduleResult, heft_rt
from repro.kernels import decision_hw, heft_rt_hw
from repro.kernels import interpret_default as _interpret_default
from repro.kernels.fused_decision import decision_ref, unpack_decision
from repro.obs.device import (
    NUM_COUNTERS,
    accumulate_counters,
    accumulate_counters_np,
    counters_dict,
    zero_counters,
)
from repro.obs.log import get_logger

_INF = float("inf")

BACKENDS = ("numpy", "jit", "pallas", "fused")

# Pallas-path fabrics warn exactly once per process when the kernels run in
# interpret mode — the fallback is correct but ~1000x slower, and it used to
# be silent (benchmarks "comparing" pallas were really timing the
# interpreter).  ``backend_effective`` exposes the same fact queryably.
_interp_warned = False


def _warn_interpret_once(backend: str) -> None:
    global _interp_warned
    if _interp_warned:
        return
    _interp_warned = True
    get_logger("fabric").warning(
        "%s backend: no compiled pallas lowering on jax backend %r — "
        "kernels run in interpret mode (correct, not fast); see "
        "MappingFabric.backend_effective", backend, jax.default_backend())


def _env_backend() -> str | None:
    """Validated ``REPRO_FABRIC_BACKEND`` value, or None when unset."""
    env = os.environ.get("REPRO_FABRIC_BACKEND", "").strip().lower()
    if env and env not in BACKENDS:
        raise ValueError(
            f"REPRO_FABRIC_BACKEND must be one of {BACKENDS}, got {env!r}")
    return env or None


def default_backend() -> str:
    """Resolve ``backend="auto"``: the ``REPRO_FABRIC_BACKEND`` env knob
    wins (the CI backend matrix pins ``pallas`` with interpret fallback);
    otherwise numpy on CPU hosts, jit when an accelerator is attached."""
    env = _env_backend()
    if env:
        return env
    return "numpy" if jax.default_backend() == "cpu" else "jit"


def _on_one_device(x):
    """``x``, or where it lies on several devices an uncommitted copy on
    the default device, through the host (a register file of a few floats).
    A device-side copy would be committed and typed by the mesh it came
    from, so the host-path kernel would trace one variant after a meshed
    tick and another after a host-path decision."""
    if len(x.sharding.device_set) > 1:
        return jnp.asarray(np.asarray(x))
    return x


def pow2_bucket(n: int, min_bucket: int = 1) -> int:
    """Next power of two ≥ ``max(n, min_bucket, 1)``.

    The one bucketing idiom every retrace-bounded dispatch in the repo
    shares: the fabric's ready-queue/PE padding (:meth:`MappingFabric
    .bucket_size`) and the paged serve runtime's active-lane padding
    (``serve.paging``) both compile O(log n_max) shape variants instead of
    one per dynamic size.
    """
    b = max(int(n), int(min_bucket), 1)
    return 1 << (b - 1).bit_length()


# ---------------------------------------------------------------------------
# Vectorized roofline front-end
# ---------------------------------------------------------------------------

def service_time_matrix(requests, replicas, *, active_params: float) -> np.ndarray:
    """Full (N, P) roofline exec-time matrix in one vectorized op.

    Bitwise-identical to looping ``service_time_s`` over (request, replica)
    pairs: prefill is compute-bound, decode is weight-streaming-bound, and
    the elementwise float64 operations associate exactly as the scalar code.
    """
    prefill = np.array([r.prefill_tokens for r in requests], dtype=np.float64)
    decode = np.array([r.decode_tokens for r in requests], dtype=np.float64)
    compute = np.array([r.compute_tflops for r in replicas], dtype=np.float64) * 1e12
    hbm = np.array([r.hbm_gbps for r in replicas], dtype=np.float64) * 1e9
    with np.errstate(divide="ignore"):
        return ((2.0 * active_params * prefill)[:, None] / compute[None, :]
                + (2.0 * active_params * decode)[:, None] / hbm[None, :])


# ---------------------------------------------------------------------------
# Oracle-exact numpy fast paths (the host side of the fabric)
# ---------------------------------------------------------------------------

def _priority_order_np(avg) -> np.ndarray:
    """Stable descending argsort, exactly as ``heft_rt_numpy`` computes it."""
    key = np.asarray(avg, dtype=np.float64)
    return np.argsort(-key, kind="stable")


def _eft_chain(rows, av):
    """The sequential EFT argmin recurrence over plain Python floats.

    ``rows``: exec times in priority order (list of lists), ``av``: the
    availability registers (mutated in place).  For the handful-of-PEs
    regime the per-step cost of the numpy version is dispatch overhead, so
    the chain runs scalar (same IEEE float64 operations, same first-minimum
    tie-break as ``np.argmin``) — bit-identical decisions.  The single
    implementation shared by :func:`heft_rt_fast` and
    :meth:`MappingFabric.assign`.
    """
    P = len(av)
    assignment, start, finish = [], [], []
    for row in rows:
        best_pe = 0
        best = av[0] + row[0]
        for p in range(1, P):
            f = av[p] + row[p]
            if f < best:
                best, best_pe = f, p
        if best < _INF:  # NaN and +inf both fail this, like np.isfinite
            assignment.append(best_pe)
            start.append(av[best_pe])
            finish.append(best)
            av[best_pe] = best
        else:
            assignment.append(-1)
            start.append(_INF)
            finish.append(_INF)
    return assignment, start, finish


def heft_rt_fast(avg, exec_times, avail):
    """Drop-in twin of :func:`repro.core.heft_rt_numpy`, ~5x faster at small P."""
    ex = np.asarray(exec_times, dtype=np.float64)
    order = _priority_order_np(avg)
    av = np.asarray(avail, dtype=np.float64).tolist()
    assignment, start, finish = _eft_chain(ex[order].tolist(), av)
    return (order, np.array(assignment, dtype=np.int64),
            np.array(start), np.array(finish), np.array(av))


def eft_dispatch_numpy(avg, exec_times, avail, capacity):
    """Early-exit HEFT_RT commit: the runtime simulator's dispatch contract.

    Follows the full priority order + EFT availability chain but only
    *commits* tasks to PEs with free worker-queue capacity, stopping once no
    capacity remains.  Prefix-identical to running :func:`heft_rt_fast` /
    ``heft_rt_numpy`` in full and committing, per PE, the first
    ``capacity[pe]`` tasks assigned to it.
    """
    ex = np.asarray(exec_times, dtype=np.float64)
    order = _priority_order_np(avg)
    av = [float(a) for a in np.asarray(avail, dtype=np.float64)]
    P = len(av)
    cap = [int(c) for c in capacity]
    remaining = sum(cap)
    out: list[tuple[int, int]] = []
    for t in order:
        if remaining == 0:
            break
        row = ex[t].tolist()
        best_pe = 0
        best = av[0] + row[0]
        for p in range(1, P):
            f = av[p] + row[p]
            if f < best:
                best, best_pe = f, p
        if not (best < _INF):
            continue
        av[best_pe] = best
        if cap[best_pe] > 0:
            out.append((int(t), best_pe))
            cap[best_pe] -= 1
            remaining -= 1
    return out


# ---------------------------------------------------------------------------
# The fabric
# ---------------------------------------------------------------------------

class MappingFabric:
    """Persistent HEFT_RT dispatch pipeline with bucketed shapes and
    device-resident availability registers.

    The P axis is *state*, not a constant: :meth:`grow` / :meth:`shrink` /
    :meth:`remap` resize or relabel the PE pool mid-stream while carrying
    the committed ``T_avail`` registers across the resize (the paper's PE
    pool whose effective composition changes at runtime).  Device backends
    pad P to a power-of-two bucket (``+inf`` exec columns, exactly like the
    queue-depth bucketing), so resize events inside a bucket reuse the
    compiled dispatch — no re-trace per event.

    Parameters
    ----------
    num_pes:
        Initial number of PEs / replicas (the variable P axis).
    backend:
        ``"numpy"`` (oracle-exact host fast path), ``"jit"`` (persistent
        jitted ``heft_rt``), ``"pallas"`` (fused overlay kernel — compiled
        on TPU/GPU, interpret-mode elsewhere), ``"fused"`` (device-resident
        PE mask + registers shareable with the paged decode tick; overlay
        kernel when a compiled lowering exists, the jnp twin otherwise), or
        ``"auto"`` — numpy on CPU hosts, jit when an accelerator backend is
        attached.
    min_bucket / max_bucket:
        Ready queues are padded to the next power of two in
        ``[min_bucket, max_bucket]``; exceeding ``max_bucket`` raises.
    min_pe_bucket:
        Smallest P bucket for the device backends (padding headroom so
        small grows stay inside one compiled variant).
    interpret:
        Force the Pallas interpret mode on/off (None: on iff not on TPU).
    avail:
        Initial availability registers (default zeros).
    tracer / metrics:
        Optional :class:`repro.obs.Tracer` / :class:`repro.obs.
        MetricsRegistry`.  When attached, every ``map_event``/``map_batch``
        records a span plus backend/bucket-labelled latency histograms
        ("fabric.event_s" per event, "fabric.decision_s" per decision — the
        paper's per-decision scheduling-latency axis), resizes emit instant
        events, and compiled-variant cache misses count as retraces.  When
        ``None`` (default) the dispatch path is exactly the uninstrumented
        code (gated by ``benchmarks/bench_obs_overhead.py``).
    device_counters:
        Accumulate scheduler counters (decisions, bucket occupancy, T_avail
        spread — see :mod:`repro.obs.device`) as extra donated registers
        *inside* the jitted dispatch; :meth:`drain_counters` reads them on
        demand with zero per-event host sync.  Decisions stay bit-identical
        to the uninstrumented oracle.
    """

    def __init__(self, num_pes: int, *, backend: str = "auto",
                 min_bucket: int = 8, max_bucket: int = 1 << 16,
                 min_pe_bucket: int = 4,
                 interpret: bool | None = None, avail=None,
                 tracer=None, metrics=None, device_counters: bool = False):
        if backend == "auto":
            backend = default_backend()
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
        self.num_pes = int(num_pes)
        self.backend = backend
        self.min_bucket = int(min_bucket)
        self.max_bucket = int(max_bucket)
        self.min_pe_bucket = int(min_pe_bucket)
        self._interpret = interpret
        self._event_fn_cached = None
        self._batch_fn_cached = None
        self._events = 0
        self._resizes = 0
        self._tracer = tracer
        self._metrics = metrics
        self._device_counters = bool(device_counters)
        self._counters = None            # device registers / host accumulator
        self._p_valid = None             # real-lane mask at the P bucket
        self._pe_mask = None             # chaos-tier unreachable-lane mask
        self._mask_dev = None            # fused backend: device mask register
        self._stage_cache = {}           # fused tick staging buffer reuse
        self._shapes_seen: set = set()   # compiled-variant keys → retraces
        self._retraces = 0
        if self._device_counters:
            self._counters = (np.zeros(NUM_COUNTERS)
                              if backend == "numpy" else zero_counters())
        if backend == "pallas" and self._interpret_resolved():
            _warn_interpret_once(backend)
        self.reset(avail)

    def _interpret_resolved(self) -> bool:
        """Whether pallas kernels dispatched by this fabric interpret."""
        if self._interpret is not None:
            return bool(self._interpret)
        return _interpret_default()

    @property
    def backend_effective(self) -> str:
        """The path that actually runs, for benchmarks/tests to assert on.

        ``"pallas-interpret"`` when the pallas backend has no compiled
        lowering on this host (the previously *silent* fallback);
        ``"fused-jnp"`` when the fused backend's decision runs as the
        traced jnp twin instead of the overlay kernel; otherwise the
        configured backend name.
        """
        if self.backend == "pallas" and self._interpret_resolved():
            return "pallas-interpret"
        if self.backend == "fused" and self._interpret_resolved():
            return "fused-jnp"
        return self.backend

    # -- availability registers ---------------------------------------------

    def reset(self, avail=None) -> None:
        """(Re)load the T_avail registers (host values → device residency)."""
        a = (np.zeros(self.num_pes) if avail is None
             else np.asarray(avail, dtype=np.float64))
        if a.shape != (self.num_pes,):
            raise ValueError(f"avail must have shape ({self.num_pes},)")
        if self.backend == "numpy":
            self._avail = a.copy()
        else:
            # Registers live padded to the P bucket on device; padded lanes
            # carry +inf exec columns in every event, so they are never
            # selected and their register values are inert.
            self._avail = jnp.asarray(self._pad_avail(a))
            # Real-lane mask for the device counters' T_avail-spread lane
            # (padded registers are inert, not meaningful load); cached on
            # device so counted dispatches do not re-upload it per event.
            self._p_valid = jnp.asarray(
                np.arange(self.p_bucket) < self.num_pes)
            if self.backend == "fused":
                # The PE mask is a device register too (padded lanes False —
                # their exec columns are already +inf), so masked dispatch
                # needs no host-side matrix copy and the mask can ride into
                # the paged decode tick's compiled program.
                self._mask_dev = jnp.asarray(self._pad_mask())

    def _pad_mask(self) -> np.ndarray:
        m = np.zeros(self.p_bucket, dtype=bool)
        if self._pe_mask is not None:
            m[: self.num_pes] = self._pe_mask
        return m

    def _pad_avail(self, a) -> np.ndarray:
        pad = np.zeros(self.p_bucket, dtype=np.float32)
        pad[: self.num_pes] = a
        return pad

    @property
    def avail(self) -> np.ndarray:
        """Current availability registers as host values (logical P only)."""
        return np.asarray(self._avail)[: self.num_pes]

    @property
    def events(self) -> int:
        """Mapping events dispatched through this fabric (single + batched)."""
        return self._events

    @property
    def resizes(self) -> int:
        """Resize events (grow/shrink/remap/resize) applied to the PE pool."""
        return self._resizes

    @property
    def retraces(self) -> int:
        """Distinct compiled-dispatch shape variants entered (device
        backends; each is one XLA trace+compile).  0 for numpy."""
        return self._retraces

    # -- observability -------------------------------------------------------

    def attach_obs(self, tracer=None, metrics=None) -> None:
        """Attach (or replace) the tracer / metrics registry after
        construction — e.g. onto the fabric a policy factory built lazily."""
        if tracer is not None:
            self._tracer = tracer
        if metrics is not None:
            self._metrics = metrics

    def drain_counters(self, *, reset: bool = True) -> dict[str, float]:
        """Read the device-resident scheduler counters (one host transfer —
        the AXI counter-file read of the paper's overlay).  ``reset`` zeroes
        the registers for the next window.  Requires
        ``device_counters=True``."""
        if not self._device_counters:
            raise ValueError(
                "fabric was built without device_counters=True")
        out = counters_dict(np.asarray(self._counters))
        if reset:
            self._counters = (np.zeros(NUM_COUNTERS)
                              if self.backend == "numpy" else zero_counters())
        return out

    @staticmethod
    def _pow2_label(n: int) -> int:
        """Power-of-two ceiling for histogram bucket labels (the numpy
        backend has no shape buckets; labelling by raw n would mint one
        histogram per queue length)."""
        return 1 << (max(int(n), 1) - 1).bit_length()

    def _note_dispatch(self, kind: str, t0: float, dt: float,
                       n: int, bucket: int) -> None:
        """Record one dispatch's latency into the attached tracer/metrics
        (called only when one is attached)."""
        if self._metrics is not None:
            self._metrics.histogram(
                "fabric.event_s", backend=self.backend,
                bucket=bucket).record(dt)
            if n > 0:
                # the paper's per-decision scheduling latency: one measured
                # event amortized over its decisions
                self._metrics.histogram(
                    "fabric.decision_s", backend=self.backend).record(
                        dt / n, n=n)
        if self._tracer is not None:
            self._tracer.complete(f"fabric.{kind}", t0, dt, n=n,
                                  bucket=bucket, backend=self.backend)

    def _note_shape(self, key: tuple) -> None:
        """Count compiled-variant cache misses (a new bucketed shape on a
        device backend is one retrace/compile)."""
        if key in self._shapes_seen:
            return
        self._shapes_seen.add(key)
        if self.backend == "numpy":
            return
        self._retraces += 1
        if self._metrics is not None:
            self._metrics.counter("fabric.retraces").inc()
        if self._tracer is not None:
            self._tracer.instant("fabric.retrace", shape=str(key),
                                 backend=self.backend)

    # -- variable-P resize events -------------------------------------------

    def grow(self, new_p: int, *, avail: float = 0.0) -> None:
        """Extend the PE pool to ``new_p`` lanes; joiners start at ``avail``.

        Existing registers are carried bit-exact; a grow inside the current
        P bucket reuses every compiled dispatch variant (the resize costs one
        host→device register reload, never a re-trace).
        """
        new_p = int(new_p)
        if new_p < self.num_pes:
            raise ValueError(
                f"grow target {new_p} < current num_pes={self.num_pes} "
                f"(use shrink(keep_idx) to drop PEs)")
        joined = np.full(new_p - self.num_pes, float(avail))
        self._set_registers(np.concatenate([self.avail, joined]), new_p)

    def shrink(self, keep_idx) -> None:
        """Drop PEs, keeping (and reordering to) ``keep_idx``.

        ``keep_idx`` lists the surviving PE indices in their new order; the
        survivors' committed availability is carried bit-exact.
        """
        keep = np.asarray(keep_idx, dtype=np.int64)
        if keep.ndim != 1 or len(keep) == 0:
            raise ValueError("keep_idx must be a non-empty 1-D index list")
        if len(np.unique(keep)) != len(keep):
            raise ValueError(f"keep_idx has duplicates: {keep.tolist()}")
        if keep.min() < 0 or keep.max() >= self.num_pes:
            raise ValueError(
                f"keep_idx {keep.tolist()} out of range for num_pes="
                f"{self.num_pes}")
        self._set_registers(self.avail[keep], len(keep))

    def remap(self, old_to_new) -> None:
        """Relabel PEs: register at old index ``i`` moves to ``old_to_new[i]``.

        ``old_to_new`` must be a permutation of ``range(num_pes)`` (replicas
        migrating between fleet slots without changing P).
        """
        perm = np.asarray(old_to_new, dtype=np.int64)
        if (perm.shape != (self.num_pes,)
                or not np.array_equal(np.sort(perm), np.arange(self.num_pes))):
            raise ValueError(
                f"old_to_new must be a permutation of range({self.num_pes}), "
                f"got {perm.tolist()}")
        new = np.empty(self.num_pes, dtype=np.float64)
        new[perm] = self.avail
        self._set_registers(new, self.num_pes)

    def resize(self, new_p: int) -> None:
        """Convenience: grow to ``new_p`` (joiners at 0) or shrink keeping
        the first ``new_p`` lanes — the policy-facing P change."""
        if new_p > self.num_pes:
            self.grow(new_p)
        elif new_p < self.num_pes:
            self.shrink(np.arange(new_p))

    def set_pe_mask(self, mask) -> None:
        """Mask PE lanes out of dispatch (the chaos tier's partition mask).

        ``mask`` is a ``(num_pes,)`` bool array — ``True`` lanes' exec
        columns dispatch as ``+inf``, so no new work maps onto them while
        their committed ``T_avail`` registers stay resident for recovery;
        ``None`` clears the mask.  Decisions with a mask are exactly the
        oracle's on the masked matrix; with no mask the dispatch path is
        untouched.  Resizes (grow/shrink/remap) clear the mask — lane
        indices change meaning, so the caller re-derives reachability.
        """
        if mask is None:
            self._pe_mask = None
        else:
            m = np.asarray(mask, dtype=bool)
            if m.shape != (self.num_pes,):
                raise ValueError(
                    f"pe mask must have shape ({self.num_pes},), got {m.shape}")
            self._pe_mask = m
        if self.backend == "fused":
            self._mask_dev = jnp.asarray(self._pad_mask())

    def _masked(self, exec_times):
        """Apply the PE mask (+inf columns); the unmasked path returns the
        input untouched — no copy, bit-identical dispatch.  The fused
        backend never host-masks: its mask is a device register applied
        inside the compiled dispatch (``where(mask, +inf, exec)``, the same
        values this copy would produce)."""
        if self._pe_mask is None or self.backend == "fused":
            return exec_times
        ex = np.array(exec_times, copy=True)
        ex[..., self._pe_mask] = _INF
        return ex

    def _set_registers(self, host_avail, new_p: int) -> None:
        old_p = self.num_pes
        self.num_pes = int(new_p)
        self._resizes += 1
        self._pe_mask = None
        self.reset(host_avail)
        if self._metrics is not None:
            self._metrics.counter("fabric.resizes").inc()
            self._metrics.gauge("fabric.num_pes").set(self.num_pes)
        if self._tracer is not None:
            self._tracer.instant("fabric.resize", old_p=old_p,
                                 new_p=self.num_pes,
                                 p_bucket=self.p_bucket)

    # -- bucketing -----------------------------------------------------------

    def bucket_size(self, n: int) -> int:
        """Next power-of-two bucket ≥ max(n, min_bucket)."""
        b = pow2_bucket(n, self.min_bucket)
        if b > self.max_bucket:
            raise ValueError(f"queue length {n} exceeds max_bucket={self.max_bucket}")
        return b

    @property
    def p_bucket(self) -> int:
        """Power-of-two P bucket the device backends pad the PE axis to."""
        b = max(self.num_pes, self.min_pe_bucket, 1)
        return 1 << (b - 1).bit_length()

    def _check_p(self, exec_times) -> None:
        if exec_times.shape[-1] != self.num_pes:
            raise ValueError(
                f"exec_times has {exec_times.shape[-1]} PE columns but the "
                f"fabric's pool is num_pes={self.num_pes} — resize the "
                f"fabric (grow/shrink) before dispatching")

    def _pad_event(self, avg, exec_times):
        """Pad one event to its buckets: sanitized keys, +inf exec (both for
        padded queue slots and padded PE lanes), valid mask."""
        n, P = exec_times.shape
        D = self.bucket_size(n)
        # NaN keys (nanmean of an all-inf row) must sort behind every finite
        # key but ahead of padding; mapping them to -inf keeps that order
        # because the stable sort breaks the tie by slot index (< n).
        a = np.full(D, -_INF, dtype=np.float32)
        a[:n] = np.where(np.isnan(avg), -_INF, np.asarray(avg, dtype=np.float32))
        # Padded PE lanes carry +inf exec: argmin's first-minimum tie-break
        # means a padded lane can never beat a real lane (finite beats inf,
        # and an all-inf row resolves to the first — real — lane, which the
        # valid/finite guard then maps to assignment -1 exactly like the
        # oracle).
        ex = np.full((D, self.p_bucket), _INF, dtype=np.float32)
        ex[:n, :P] = exec_times
        valid = np.arange(D) < n
        return a, ex, valid

    # -- compiled dispatch cache --------------------------------------------

    def _event_fn(self):
        # One callable serves every bucket: jit specializes per shape
        # internally, and the pallas wrapper is shape-agnostic.  With
        # device_counters the compiled program carries the counter registers
        # as an extra donated argument and folds the decision outputs into
        # them in the same dispatch (see repro.obs.device) — the schedule
        # outputs are untouched.
        if self._event_fn_cached is None:
            counted = self._device_counters
            if self.backend == "fused":
                decide = self._fused_decide()

                if counted:
                    def counted_fused(avg, ex, avail, valid, mask, counters,
                                      p_valid):
                        res = decide(avg, ex, avail, valid, mask)
                        return res, accumulate_counters(
                            counters, res.assignment, res.new_avail, valid,
                            p_valid)

                    fn = jax.jit(counted_fused, donate_argnums=(2, 5))
                else:
                    fn = jax.jit(decide, donate_argnums=(2,))
            elif self.backend == "pallas":
                interp = self._interpret

                if counted:
                    def fn(avg, ex, avail, valid, counters, p_valid):
                        res = ScheduleResult(*heft_rt_hw(avg, ex, avail,
                                                         interpret=interp))
                        return res, accumulate_counters(
                            counters, res.assignment, res.new_avail,
                            valid, p_valid)
                else:
                    def fn(avg, ex, avail, valid):  # valid baked into padding
                        return ScheduleResult(*heft_rt_hw(avg, ex, avail,
                                                          interpret=interp))
            elif counted:
                def counted_event(avg, ex, avail, valid, counters, p_valid):
                    res = heft_rt(avg, ex, avail, valid)
                    return res, accumulate_counters(
                        counters, res.assignment, res.new_avail, valid,
                        p_valid)

                fn = jax.jit(counted_event, donate_argnums=(2, 4))
            else:
                # donate_argnums keeps T_avail device-resident: the register
                # file buffer is reused for new_avail instead of copied.
                fn = jax.jit(heft_rt, donate_argnums=(2,))
            self._event_fn_cached = fn
        return self._event_fn_cached

    def _fused_decide(self):
        """The fused backend's per-event decision body: the overlay kernel
        (:func:`repro.kernels.decision_hw`, in-kernel mask row) when a
        compiled pallas lowering exists on this host; otherwise the
        bit-identical jnp twin :func:`repro.kernels.fused_decision
        .decision_ref` — interpret-mode pallas would be a latency own-goal,
        and the twin traces straight into the decode tick's program."""
        if not self._interpret_resolved():
            def decide(avg, ex, avail, valid, mask):
                del valid  # baked into the -inf-key / +inf-exec padding
                return ScheduleResult(*decision_hw(avg, ex, avail, mask,
                                                   interpret=False))
            return decide
        return decision_ref

    def _batch_fn(self):
        if self._batch_fn_cached is None:
            counted = self._device_counters
            if self.backend == "fused":
                decide = self._fused_decide()
                inner = jax.vmap(decide, in_axes=(0, 0, 0, 0, None))

                if counted:
                    def counted_fused_b(avg, ex, avail, valid, mask, counters,
                                        p_valid):
                        res = inner(avg, ex, avail, valid, mask)
                        return res, accumulate_counters(
                            counters, res.assignment, res.new_avail, valid,
                            p_valid)

                    fn = jax.jit(counted_fused_b, donate_argnums=(2, 5))
                else:
                    fn = jax.jit(inner, donate_argnums=(2,))
            elif self.backend == "pallas":
                interp = self._interpret
                inner = jax.vmap(
                    lambda a, e, v: ScheduleResult(*heft_rt_hw(a, e, v,
                                                               interpret=interp)))

                if counted:
                    def fn(avg, ex, avail, valid, counters, p_valid):
                        res = inner(avg, ex, avail)
                        return res, accumulate_counters(
                            counters, res.assignment, res.new_avail,
                            valid, p_valid)
                else:
                    def fn(avg, ex, avail, valid):
                        return inner(avg, ex, avail)
            elif counted:
                def counted_batch(avg, ex, avail, valid, counters, p_valid):
                    res = jax.vmap(heft_rt)(avg, ex, avail, valid)
                    return res, accumulate_counters(
                        counters, res.assignment, res.new_avail, valid,
                        p_valid)

                fn = jax.jit(counted_batch, donate_argnums=(2, 4))
            else:
                fn = jax.jit(jax.vmap(heft_rt), donate_argnums=(2,))
            self._batch_fn_cached = fn
        return self._batch_fn_cached

    def _dispatch_event(self, fn, a_p, ex_p, av_in, valid):
        """Run one compiled dispatch, threading the device counter
        registers (and, for the fused backend, the device mask register)
        through when enabled."""
        if self.backend == "fused":
            # A meshed decode tick hands the registers back replicated over
            # its devices; the host path's kernel is a Mosaic call, which
            # cannot be partitioned, so it takes them on one device.
            av_in = _on_one_device(av_in)
            if self._device_counters:
                self._counters = _on_one_device(self._counters)
                res, self._counters = fn(a_p, ex_p, av_in, valid,
                                         self._mask_dev, self._counters,
                                         self._p_valid)
                return res
            # Exclusive branches: exactly one dispatch runs per event, so
            # av_in is donated exactly once (and the mask register is never
            # in this jit's donate set).
            return fn(a_p, ex_p, av_in, valid,  # repro: noqa[donation-after-use]
                      self._mask_dev)  # repro: noqa[donation-after-use]
        if self._device_counters:
            res, self._counters = fn(a_p, ex_p, av_in, valid,  # repro: noqa[donation-after-use]
                                     self._counters, self._p_valid)
            return res
        # Exclusive else-branch of the counted call above — only one of the
        # two dispatches runs, so av_in is donated exactly once.
        return fn(a_p, ex_p, av_in, valid)  # repro: noqa[donation-after-use]

    # -- mapping events ------------------------------------------------------

    def map_event(self, avg, exec_times, avail=None, *, update: bool | None = None):
        """One HEFT_RT mapping event.

        ``avail=None`` uses (and by default updates) the fabric's resident
        availability registers; passing ``avail`` explicitly leaves the
        registers untouched unless ``update=True``.

        Returns ``(order, assignment, start, finish, new_avail)`` as host
        arrays trimmed to the real queue length — the ``heft_rt_numpy``
        contract, in priority order.
        """
        exec_times = self._masked(np.asarray(exec_times))
        avg = np.asarray(avg)
        self._check_p(exec_times)
        n = exec_times.shape[0]
        use_resident = avail is None
        if update is None:
            update = use_resident
        self._events += 1
        obs_on = self._metrics is not None or self._tracer is not None
        t0 = time.perf_counter() if obs_on else 0.0
        if self.backend == "numpy":
            av_in = self._avail if use_resident else np.asarray(avail)
            out = heft_rt_fast(avg, exec_times, av_in)
            if update:
                self._avail = out[4].copy()
            if self._device_counters:
                accumulate_counters_np(self._counters, out[1], out[4])
            if obs_on:
                self._note_dispatch("map_event", t0,
                                    time.perf_counter() - t0, n,
                                    self._pow2_label(n))
            return out
        a_p, ex_p, valid = self._pad_event(avg, exec_times)
        self._note_shape(("event", len(a_p), self.p_bucket))
        if use_resident:
            # The register file is donated to the call; when the caller wants
            # the registers left alone, donate a copy instead.
            av_in = self._avail if update else jnp.array(self._avail, copy=True)
        else:
            av_in = jnp.asarray(
                self._pad_avail(np.asarray(avail, dtype=np.float64)))
        res = self._dispatch_event(self._event_fn(), a_p, ex_p, av_in, valid)
        if update:
            self._avail = res.new_avail
        out = (np.asarray(res.order)[:n], np.asarray(res.assignment)[:n],
               np.asarray(res.start_time)[:n], np.asarray(res.finish_time)[:n],
               np.asarray(res.new_avail)[: self.num_pes])
        if obs_on:
            self._note_dispatch("map_event", t0, time.perf_counter() - t0,
                                n, len(a_p))
        return out

    def map_batch(self, avg, exec_times, avail) -> ScheduleResult:
        """Batched mapping events: one device dispatch for B independent
        ready queues (the fabric-batched pipeline).

        ``avg``: (B, D), ``exec_times``: (B, D, P), ``avail``: (B, P).
        Returns a device-resident :class:`ScheduleResult` with leading batch
        dimension, trimmed to the input D.  With the numpy backend this
        loops the host oracle (useful as a reference, not for speed).
        """
        avg = np.asarray(avg)
        exec_times = self._masked(np.asarray(exec_times))
        avail_np = np.asarray(avail)
        self._check_p(exec_times)
        B, D = avg.shape
        self._events += B
        obs_on = self._metrics is not None or self._tracer is not None
        t0 = time.perf_counter() if obs_on else 0.0
        if self.backend == "numpy":
            outs = [heft_rt_fast(avg[i], exec_times[i], avail_np[i])
                    for i in range(B)]
            out = ScheduleResult(*(np.stack(cols) for cols in zip(*outs)))
            if self._device_counters:
                accumulate_counters_np(self._counters, out.assignment,
                                       out.new_avail)
            if obs_on:
                self._note_dispatch("map_batch", t0,
                                    time.perf_counter() - t0, B * D,
                                    self._pow2_label(D))
            return out
        Db = self.bucket_size(D)
        Bb = self.bucket_size(B)
        Pb = self.p_bucket
        self._note_shape(("batch", Bb, Db, Pb))
        a_p = np.full((Bb, Db), -_INF, dtype=np.float32)
        a_p[:B, :D] = np.where(np.isnan(avg), -_INF, avg)
        ex_p = np.full((Bb, Db, Pb), _INF, dtype=np.float32)
        ex_p[:B, :D, : self.num_pes] = exec_times
        av_p = np.zeros((Bb, Pb), dtype=np.float32)
        av_p[:B, : self.num_pes] = avail_np
        valid = np.zeros((Bb, Db), dtype=bool)
        valid[:B, :D] = True
        res = self._dispatch_event(self._batch_fn(), a_p, ex_p,
                                   jnp.asarray(av_p), valid)
        out = ScheduleResult(res.order[:B, :D], res.assignment[:B, :D],
                             res.start_time[:B, :D], res.finish_time[:B, :D],
                             res.new_avail[:B, : self.num_pes])
        if obs_on:
            self._note_dispatch("map_batch", t0, time.perf_counter() - t0,
                                B * D, Db)
        return out

    # -- consumer-facing contracts ------------------------------------------

    def assign(self, exec_times, avail) -> np.ndarray:
        """Serving-policy contract: ready-order replica assignment (n,).

        ``avg`` is the mean exec time across replicas (the serving
        scheduler's Avg_TID), exactly as ``policy_heft_rt`` computes it.
        (The key must be the *mean*, not the row sum: float division is not
        injective, so distinct sums can collide into one mean — tie sets
        would differ from the oracle's.  ``sum/P`` is bitwise ``np.mean``
        — same pairwise sum, same divide — minus the reduction-machinery
        overhead.)
        """
        exec_times = self._masked(np.asarray(exec_times))
        self._check_p(exec_times)
        n, P = exec_times.shape
        if self.backend == "numpy":
            ex = np.asarray(exec_times, dtype=np.float64)
            self._events += 1
            obs_on = self._metrics is not None or self._tracer is not None
            t0 = time.perf_counter() if obs_on else 0.0
            order = np.argsort(-(ex.sum(axis=1) / P), kind="stable")
            av = np.asarray(avail, dtype=np.float64).tolist()
            assignment, _, _ = _eft_chain(ex[order].tolist(), av)
            if self._device_counters:
                accumulate_counters_np(self._counters,
                                       np.asarray(assignment),
                                       np.asarray(av))
            if obs_on:
                self._note_dispatch("assign", t0, time.perf_counter() - t0,
                                    n, self._pow2_label(n))
        else:
            order, assignment, _, _, _ = self.map_event(
                exec_times=exec_times, avg=exec_times.mean(axis=1),
                avail=avail, update=False)
        out = np.empty(n, dtype=np.int64)
        out[order] = assignment
        return out

    def dispatch(self, avg, exec_times, avail, capacity) -> list[tuple[int, int]]:
        """Runtime-simulator contract: early-exit capacity-limited commit.

        Identical decisions to :func:`eft_dispatch_numpy` (and hence to the
        seed ``dispatch_heft_rt``): the device backends run the full mapping
        event and commit, per PE, the first ``capacity[pe]`` tasks in
        priority order until total capacity is exhausted.
        """
        if self.backend == "numpy":
            return eft_dispatch_numpy(avg, self._masked(np.asarray(exec_times)),
                                      avail, capacity)
        order, assignment, _, _, _ = self.map_event(avg, exec_times, avail,
                                                    update=False)
        cap = [int(c) for c in capacity]
        remaining = sum(cap)
        out: list[tuple[int, int]] = []
        for qid, pe in zip(order, assignment):
            if remaining == 0:
                break
            if pe >= 0 and cap[pe] > 0:
                out.append((int(qid), int(pe)))
                cap[pe] -= 1
                remaining -= 1
        return out


    # -- fused-tick register sharing ----------------------------------------
    #
    # The paged decode tick (serve/paging.py) inlines the HEFT_RT decision
    # into its own compiled program; these two methods are the fabric's side
    # of that contract.  The device registers (T_avail, PE mask, counter
    # file) stay owned by the fabric — the tick borrows them for one
    # dispatch and hands the donated results back — so every resident-state
    # contract (resize carries registers bit-exact, set_pe_mask, drain_
    # counters) keeps working unchanged while decisions ride the tick.

    def tick_decision_inputs(self, avg, exec_times):
        """Stage one mapping event for a fused decode tick.

        Pads ``(avg, exec_times)`` to this fabric's buckets and returns
        ``(a_p, ex_p, valid, avail, mask, counters, p_valid)`` — the padded
        operands plus the live device registers for the tick's compiled
        program to consume.  ``avail`` (and ``counters``) are the resident
        buffers and will be *donated* to the tick: the caller must follow
        up with :meth:`commit_tick_decision` on the tick's outputs before
        the next dispatch.  ``counters``/``p_valid`` are ``None`` when the
        fabric was built without ``device_counters``.  Fused backend only.
        """
        if self.backend != "fused":
            raise ValueError(
                f"tick fusion requires backend='fused', got {self.backend!r}")
        avg = np.asarray(avg)
        exec_times = np.asarray(exec_times)
        self._check_p(exec_times)
        n, P = exec_times.shape
        D = self.bucket_size(n)
        # Steady-state fast path: the padded staging buffers are reused
        # across ticks (the jit boundary copies them into device memory
        # synchronously at dispatch, so in-place refills are safe).  Only
        # the live region changes between events of the same shape; the
        # padding lanes were written once by _pad_event and are invariant.
        cached = self._stage_cache.get((D, self.p_bucket))
        if cached is None or cached[3] != (n, P):
            a_p, ex_p, valid = self._pad_event(avg, exec_times)
            self._stage_cache[(D, self.p_bucket)] = [a_p, ex_p, valid, (n, P)]
        else:
            a_p, ex_p, valid, _ = cached
            a_p[:n] = np.where(np.isnan(avg),
                               -_INF, np.asarray(avg, dtype=np.float32))
            ex_p[:n, :P] = exec_times
        self._note_shape(("event", D, self.p_bucket))
        counted = self._device_counters
        return (a_p, ex_p, valid, self._avail, self._mask_dev,
                self._counters if counted else None,
                self._p_valid if counted else None)

    def commit_tick_decision(self, n: int, buf, new_avail, counters=None):
        """Adopt a fused tick's decision outputs back into the fabric.

        ``buf`` is the *host* copy of the tick's packed decision lanes —
        :func:`repro.kernels.fused_decision.pack_tick_outputs`' layout with
        the token prefix already sliced off (``order | assignment | start |
        finish | new_avail`` as raw int32, float lanes bitcast).
        ``new_avail`` is the program's *device-resident* register output
        (it reuses the donated buffer, so residency is preserved with zero
        copies) and becomes the live register file; ``counters``, when
        given, the accumulated counter registers.  Returns the host-trimmed
        ``(order, assignment, start, finish, new_avail)`` tuple — the
        :meth:`map_event` contract for the ``n`` real queue slots,
        recovered by zero-copy ``.view`` (bit-identical, no extra device
        sync).
        """
        if self.backend != "fused":
            raise ValueError(
                f"tick fusion requires backend='fused', got {self.backend!r}")
        self._events += 1
        self._avail = new_avail
        if counters is not None:
            self._counters = counters
        order, assignment, start, finish, avail = unpack_decision(
            buf, self.p_bucket)
        return (order[:n], assignment[:n], start[:n], finish[:n],
                avail[: self.num_pes])


def make_policy_fabric(backend: str | None = None, *, tracer=None,
                       metrics=None, device_counters: bool = False):
    """Serving-policy factory backed by a :class:`MappingFabric`.

    The returned policy matches ``policy_heft_rt`` decision-for-decision;
    the fabric is created lazily so one factory works for any fleet size,
    and a *fleet-size change mid-stream* (elastic resize events) resizes the
    live fabric instead of rebuilding it — the compiled dispatch variants
    survive every resize inside a P bucket.  ``backend=None`` honours
    ``REPRO_FABRIC_BACKEND`` (the CI backend matrix) and defaults to the
    oracle-exact numpy host path otherwise.

    ``tracer``/``metrics``/``device_counters`` thread the observability
    layer into the lazily built fabric (see :class:`MappingFabric`); the
    fabric is reachable afterwards via the policy's ``fabric()`` attribute
    (None until the first mapping event).
    """
    if backend is None:
        backend = _env_backend() or "numpy"
    fab: MappingFabric | None = None

    def policy(exec_times, avail):
        nonlocal fab
        if fab is None:
            fab = MappingFabric(exec_times.shape[1], backend=backend,
                                tracer=tracer, metrics=metrics,
                                device_counters=device_counters)
        elif fab.num_pes != exec_times.shape[1]:
            # registers are irrelevant here (the policy passes avail
            # explicitly), so the prefix-keeping resize is safe
            fab.resize(exec_times.shape[1])
        return fab.assign(exec_times, avail)

    policy.fabric = lambda: fab
    return policy
