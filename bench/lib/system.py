"""The system under test, built and driven the way the launcher serves.

``build`` makes the fleet through the program's public constructors
(``ServeEngine``/``ReplicaHandle`` sharing one weight tree, or
``mesh_backed_fleet`` for a sharded replica), a fused-backend
``MappingFabric`` and a ``HeftFrontEnd``.  The weights come from the
configuration's plain reference module, made from ``--seed`` on the device.

``serve`` drives ``HeftFrontEnd.run_continuous(fused=True)`` -- the loop,
the tick and the model are the program's own.  Arrivals are open-loop on
the wall clock: each request's entry in ``arrival_ticks`` is a ``Due``
whose ``<=`` against the loop's tick counter is true once the clock has
reached the request's due time, so a request becomes visible at the first
loop iteration after it is due.  Timestamps come from wrappers installed on
each replica's engine (``admit``: admission and first token; ``decode_tick``:
one time per slot in the tick's result; ``retire``: completion) and on the
fabric's host-path ``map_event``; they also keep the scheduling decisions
for the correctness check.
"""

from __future__ import annotations

import math
import shutil
import tempfile
import time
from contextlib import ExitStack
from dataclasses import dataclass, field

import numpy as np


# ---------------------------------------------------------------------------
# building
# ---------------------------------------------------------------------------

def model_config(config: dict):
    """The program's ``ModelConfig`` for a llama-architecture configuration
    file: its published config with every size taken from the file."""
    from repro.configs import get_config

    if config.get("model_type") != "llama" or config.get("hidden_act") != \
            "silu":
        raise ValueError("only llama-architecture (silu) configurations map "
                         "onto the program's ModelConfig here")
    if config.get("tie_word_embeddings"):
        raise ValueError("tied embeddings are not mapped")
    d, h = config["hidden_size"], config["num_attention_heads"]
    return get_config(config["program_arch"]).with_(
        num_layers=config["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=config["num_key_value_heads"], head_dim=d // h,
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        param_dtype=config["torch_dtype"],
        compute_dtype=config["torch_dtype"])


@dataclass
class System:
    cfg: object                 # the program's ModelConfig
    hp: dict                    # the reference's sizes
    ref: object                 # the reference module
    front: object               # HeftFrontEnd
    fabric: object              # MappingFabric (fused)
    params: dict                # the weights (made by the reference)
    weight_shardings: object    # None on one chip
    serving: dict
    chips: int

    @property
    def replicas(self):
        return self.front.replicas


def weight_key(seed: int):
    """A PRNG key from any whole number (``jax.random.key`` keeps only the
    low 32 bits of an int, so the seed goes through a SeedSequence)."""
    import jax

    from .traffic import seed_sequence

    lo, hi = seed_sequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(lo)), int(hi))


def build(config: dict, ref, seed: int, chips: int) -> System:
    import jax

    from repro.dist.sharding import MeshAxes, named, replica_pspecs
    from repro.launch.mesh import make_mesh
    from repro.models.model import param_specs
    from repro.sched_integration.fabric import MappingFabric
    from repro.serve import (HeftFrontEnd, ReplicaHandle, ServeEngine,
                             mesh_backed_fleet)

    cfg = model_config(config)
    hp = ref.sizes(config)
    serving = config["serving"]
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)), param_specs(cfg))
    have = jax.tree.map(lambda s: (s.shape, str(s.dtype)),
                        ref.weight_shapes(hp))
    if want != have:
        raise ValueError("the reference's weight layout differs from the "
                         f"program's: {have} vs {want}")
    key = weight_key(seed)
    mesh_shape = serving.get("mesh")
    if mesh_shape:
        shape = tuple(int(x) for x in mesh_shape.split("x"))
        if math.prod(shape) != chips:
            raise ValueError(f"mesh {mesh_shape} does not use {chips} chips")
        mesh = make_mesh(shape, ("data", "model"),
                         devices=jax.devices()[:chips])
        shardings = named(mesh, replica_pspecs(cfg, MeshAxes())["params"])
        params = ref.make_weights(key, hp, shardings)
        fleet = mesh_backed_fleet(cfg, params,
                                  [shape] * serving["replicas"],
                                  max_len=serving["max_len"],
                                  devices=jax.devices()[:chips])
    else:
        shardings = None
        params = ref.make_weights(key, hp)
        speeds = serving.get("speeds", [1.0] * serving["replicas"])
        fleet = [ReplicaHandle(f"replica{i}",
                               ServeEngine(cfg, params,
                                           max_len=serving["max_len"]),
                               speed=float(speed))
                 for i, speed in enumerate(speeds)]
    fabric = MappingFabric(len(fleet), backend="fused", device_counters=True)
    front = HeftFrontEnd(fleet, fabric=fabric)
    for r in fleet:
        r.engine.start_paged(max_batch=serving["max_batch"],
                             page_size=serving["page_size"])
    return System(cfg, hp, ref, front, fabric, params, shardings, serving,
                  chips)


def set_weights(system: System, seed: int) -> None:
    """Replace the weights in place (same shapes and placement, so nothing
    recompiles): the limit-setting tool's way to try many seeds in one
    process."""
    system.params = None          # the old weights go first: two sets
    for r in system.replicas:     # of 7B weights do not fit one chip
        r.engine.params = None
    params = system.ref.make_weights(weight_key(seed), system.hp,
                                     system.weight_shardings)
    system.params = params
    for r in system.replicas:
        r.engine.params = params


def _lane_buckets(max_batch: int) -> list[int]:
    return [1 << i for i in range(int(math.log2(max_batch)) + 1)]


def warm(system: System, grid: list[int]) -> None:
    """Run every program shape the cell's traffic uses, once: each grid
    length's prefill on each replica, the decode tick at each lane bucket
    plain and with a fused decision of one and of two queue buckets, and
    the fabric's host-path decision at both buckets."""
    fab = system.fabric
    p = len(system.replicas)
    events = [(np.ones(n), np.ones((n, p))) for n in (1, fab.min_bucket + 1)]
    for r in system.replicas:
        eng = r.engine
        for n in grid:
            eng.retire(eng.admit(np.zeros(n, np.int32), 1))
        slots = []
        for b in _lane_buckets(system.serving["max_batch"]):
            while len(slots) < b:
                slots.append(eng.admit(np.zeros(grid[0], np.int32), 32))
            eng.decode_tick()
            for avg, ex in events:
                eng.decode_tick((avg, ex, fab))
        for s in slots:
            eng.retire(s)
    for avg, ex in events:
        fab.map_event(avg, ex)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

class Due:
    """A request's due time on the host clock, as ``run_continuous``'s
    arrival hook: ``due <= tick`` is true once the clock has reached it.
    ``seen`` is when the loop first found it due."""

    __slots__ = ("t", "seen")

    def __init__(self, t: float):
        self.t = t
        self.seen = None

    def __le__(self, tick) -> bool:
        if self.seen is not None:
            return True
        now = time.perf_counter()
        if now >= self.t:
            self.seen = now
            return True
        return False

    def __lt__(self, other: "Due") -> bool:
        return self.t < other.t

    def __eq__(self, other) -> bool:
        return isinstance(other, Due) and self.t == other.t

    __hash__ = object.__hash__


@dataclass
class Served:
    """One request as the harness saw it (host clock, seconds)."""

    rid: int
    prompt: np.ndarray
    new_tokens: int
    due: float
    in_window: bool
    replica: int = -1
    admit_t0: float = math.nan          # start of the admit call
    token_t: list = field(default_factory=list)   # one time per token
    done_t: float = math.nan
    output: np.ndarray | None = None    # prompt + generated tokens
    visible_t: float = math.nan         # when the loop first found it due


@dataclass
class Tick:
    replica: int
    t0: float
    t1: float
    positions: tuple                    # write position of each live lane
    profiled: bool


class Recorder:
    """The harness's wrappers on each engine and on the fabric."""

    def __init__(self, system: System, reqs: list[Served], *,
                 profile=None):
        self.by_prompt = {id(r.prompt): r for r in reqs}
        self.slot_req = [dict() for _ in system.replicas]
        self.ticks: list[Tick] = []
        self.decisions: list[tuple] = []    # (kind, avg, exec, decision)
        self.refused = 0
        self.profile = profile              # Profile, or None
        self._undo = []
        for i, r in enumerate(system.replicas):
            self._wrap(r.engine, i)
        fab = system.fabric
        orig_map = fab.map_event

        def map_event(avg, exec_times, *a, **kw):
            with self._annotate("fabric.map_event"):
                out = orig_map(avg, exec_times, *a, **kw)
            self.decisions.append(("host", np.array(avg),
                                   np.array(exec_times), out))
            return out

        fab.map_event = map_event
        self._undo.append(lambda: delattr(fab, "map_event"))

    def _annotate(self, name):
        if self.profile is not None and self.profile.active:
            import jax

            return jax.profiler.TraceAnnotation(name)
        return ExitStack()

    def _wrap(self, eng, i: int) -> None:
        slots = self.slot_req[i]
        orig_admit, orig_tick, orig_retire = (eng.admit, eng.decode_tick,
                                              eng.retire)

        def admit(prompt, new_tokens):
            r = self.by_prompt[id(prompt)]
            t0 = time.perf_counter()
            with self._annotate("engine.admit"):
                slot = orig_admit(prompt, new_tokens)
            t1 = time.perf_counter()
            if slot is None:
                self.refused += 1
                return None
            r.replica, r.admit_t0 = i, t0
            r.token_t.append(t1)
            slots[slot] = r
            return slot

        def decode_tick(sched=None):
            live = [r for r in slots.values()
                    if len(r.token_t) < r.new_tokens]
            if self.profile is not None:
                self.profile.poll()
            if not live:
                return orig_tick(sched)
            pos = tuple(len(r.prompt) + len(r.token_t) - 1 for r in live)
            t0 = time.perf_counter()
            with self._annotate("engine.decode_tick"):
                res = orig_tick(sched)
            t1 = time.perf_counter()
            toks = res if sched is None else res[0]
            for s in toks:
                slots[s].token_t.append(t1)
            self.ticks.append(Tick(i, t0, t1, pos, self.profile is not None
                                   and self.profile.active))
            if sched is not None:
                self.decisions.append(("tick", np.array(sched[0]),
                                       np.array(sched[1]), res[1]))
            return res

        def retire(slot):
            out = orig_retire(slot)
            r = slots.pop(slot)
            r.done_t = time.perf_counter()
            r.output = np.asarray(out)
            return out

        eng.admit, eng.decode_tick, eng.retire = admit, decode_tick, retire
        for name in ("admit", "decode_tick", "retire"):
            self._undo.append(lambda e=eng, n=name: delattr(e, n))

    def uninstall(self) -> None:
        for undo in reversed(self._undo):
            undo()
        self._undo = []


class Profile:
    """A ``jax.profiler`` trace of a sub-window, started and stopped from
    the serving loop (the decode-tick wrapper polls it every iteration),
    with a ``bench.window`` annotation marking what it covers."""

    def __init__(self, start_t: float, stop_t: float):
        self.start_t, self.stop_t = start_t, stop_t
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.active = False
        self.done = False
        self._ann = None

    def poll(self) -> None:
        if self.done:
            return
        now = time.perf_counter()
        if not self.active and now >= self.start_t:
            self.start()
        elif self.active and now >= self.stop_t:
            self.stop()

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.dir)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self.active = True

    def stop(self) -> None:
        import jax

        if not self.active:
            return
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def xplane(self) -> str | None:
        import glob

        found = glob.glob(f"{self.dir}/plugins/profile/*/*.xplane.pb")
        return found[0] if found else None

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


@dataclass
class RunRecord:
    reqs: list
    rec: Recorder
    t_base: float               # where the lead-in starts
    w0: float                   # the measured window
    w1: float
    stats: dict
    avail0: np.ndarray
    spans: list = field(default_factory=list)   # (name, t0, dur, args)

    @property
    def window_reqs(self):
        return [r for r in self.reqs if r.in_window]


def serve(system: System, schedule, *, lead_in_s: float, seconds: float,
          profile_at: tuple[float, float] | None = None,
          tracer=None) -> RunRecord:
    """Serve ``schedule`` (``traffic.Request``s) once, open loop.

    ``profile_at``: (offset, length) of a profiled sub-window, counted
    from the start of the measured window.  ``tracer``: a program
    ``Tracer`` attached to the front end and every engine for the run.
    """
    front = system.front
    reqs = [Served(q.rid, q.prompt, q.new_tokens, q.offset_s, q.in_window)
            for q in schedule]
    for r in system.replicas:
        r.avail_at = 0.0
    avail0 = np.zeros(len(system.replicas), np.float32)
    if tracer is not None:
        front.tracer = tracer
        for r in system.replicas:
            r.engine.tracer = tracer
    t_base = time.perf_counter()
    w0 = t_base + lead_in_s
    profile = None
    if profile_at is not None:
        profile = Profile(w0 + profile_at[0], w0 + sum(profile_at))
    rec = Recorder(system, reqs, profile=profile)
    dues = []
    for r in reqs:
        r.due = t_base + r.due
        dues.append(Due(r.due))
    try:
        _, stats = front.run_continuous(
            [(r.prompt, r.new_tokens) for r in reqs], arrival_ticks=dues,
            max_batch=system.serving["max_batch"],
            page_size=system.serving["page_size"], fused=True)
    finally:
        rec.uninstall()
        if profile is not None and profile.active:
            profile.stop()
        if tracer is not None:
            front.tracer = None
            for r in system.replicas:
                r.engine.tracer = None
    for r, d in zip(reqs, dues):
        r.visible_t = d.seen if d.seen is not None else math.nan
    run = RunRecord(reqs, rec, t_base, w0, w0 + seconds, stats, avail0)
    if tracer is not None:
        epoch = time.perf_counter() - tracer.now_us() / 1e6
        run.spans = [(e.name, epoch + e.ts / 1e6, e.dur / 1e6, e.args or {})
                     for e in tracer.events() if e.ph == "X"]
    return run


def free(system: System) -> None:
    """Drop the program's device state (page pools, fabric registers), so
    the reference that follows runs with the weights alone."""
    for r in system.replicas:
        rt = r.engine.paged
        if rt is not None:
            rt.pool.pools = None
    system.fabric.reset()
