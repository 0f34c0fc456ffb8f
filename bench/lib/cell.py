"""One run of one cell: set up, serve the measured window, check, report.

``run`` is what ``bench/run.py`` calls.  It prints one JSON object as the
last line of standard output:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

With ``trace=False`` the metrics are the cell's end-to-end metrics, taken
with every instrument off; with ``trace=True`` they are its per-layer
metrics, read from the program's spans, the harness's timestamps and a
profiler trace of a sub-window of the measured window.
"""

from __future__ import annotations

import json
import sys
import time
from types import SimpleNamespace

from . import check, roofline, spec, system, timings, traffic

TRACER_CAPACITY = 1 << 19
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoAccelerator(RuntimeError):
    pass


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``<checkout>/.jax_cache``,
    or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program
    however quick its compile."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as enable

    where = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


class CompileLog:
    """Times of traces, backend compiles and persistent-cache hits."""

    def __init__(self):
        import jax

        self.events: list[tuple[float, str]] = []

        def on_duration(event, secs, **_):
            if event in COMPILE_EVENTS:
                self.events.append((time.perf_counter(), event))

        def on_event(event, **_):
            if event == CACHE_HIT:
                self.events.append((time.perf_counter(), event))

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def between(self, t0: float, t1: float) -> dict:
        out = {"traces": 0, "compiles": 0, "cache_hits": 0}
        names = dict(zip(COMPILE_EVENTS + (CACHE_HIT,),
                         ("traces", "compiles", "cache_hits")))
        for t, e in self.events:
            if t0 <= t <= t1:
                out[names[e]] += 1
        return out


def peak_memory(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def per_layer(cell, run, sys_, summary, peak) -> dict:
    """Each per-layer metric whose reader finds something to read."""
    w0, w1 = run.w0, run.w1
    view = SimpleNamespace(
        window_reqs=run.window_reqs,
        ticks=[t for t in run.rec.ticks if w0 <= t.t0 < w1],
        profiled_ticks=[t for t in run.rec.ticks if t.profiled],
        spans=[s for s in run.spans if w0 <= s[1] < w1],
        trace=summary, hp=sys_.hp, serving=sys_.serving, peak=peak,
        chips=sys_.chips, w0=w0, w1=w1, roofline=roofline)
    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"])(view)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
        process_start: float, require_tpu: bool = True,
        out=None, err=None) -> int:
    out = out or sys.stdout
    err = err or sys.stderr

    def say(msg):
        print(msg, file=err, flush=True)

    try:
        devs = devices_for(cell.chips, require_tpu)
    except NoAccelerator as e:
        say(f"bench: {e}")
        return 3
    dev = devs[0]
    if require_tpu:
        say(f"compile cache: {enable_compile_cache()}")
    compiles = CompileLog()
    ref = spec.reference_module(cell.config)
    params = cell.params
    sys_ = system.build(cell.config, ref, seed, cell.chips)
    grid = traffic.grid_lengths(cell.traffic)
    if traffic.max_total(cell.traffic) > sys_.serving["max_len"]:
        raise ValueError("the traffic's longest request does not fit a slot")
    system.warm(sys_, grid)
    sched = traffic.schedule(
        cell.traffic, rate_per_s=params["rate_per_s"],
        lead_in_s=params["lead_in_s"], seconds=seconds, seed=seed,
        vocab_size=sys_.hp["v"])
    tracer = None
    profile_at = None
    if trace:
        from repro.obs import Tracer

        tracer = Tracer(capacity=TRACER_CAPACITY)
        span = min(params["profile_s"], seconds)
        profile_at = ((seconds - span) / 2, span)
    run_ = system.serve(sys_, sched, lead_in_s=params["lead_in_s"],
                        seconds=seconds, profile_at=profile_at,
                        tracer=tracer)
    peak = peak_memory(devs)
    setup_s = run_.t_base - process_start
    win = run_.window_reqs

    e2e = timings.end_to_end(win, run_.w0, run_.w1, setup_s)
    say("end to end: " + json.dumps(e2e))
    say("generator lateness ms: " + json.dumps(timings.lateness_ms(win)))
    say("compiles after set-up: " + json.dumps(
        compiles.between(run_.t_base, time.perf_counter())))
    say(f"requests: {len(run_.reqs)} ({len(win)} in the window), "
        f"{len(run_.rec.ticks)} ticks, {run_.rec.refused} refused admits, "
        f"{run_.stats['fused_decisions']} fused + "
        f"{run_.stats['host_decisions']} host decisions")

    if trace:
        from . import trace as trace_mod

        path = run_.rec.profile.xplane()
        summary = (trace_mod.reduce(path, cell.chips)
                   if path and require_tpu else None)
        run_.rec.profile.cleanup()
        if tracer.dropped:
            say(f"tracer dropped {tracer.dropped} events")
        metrics = per_layer(cell, run_, sys_, summary,
                            spec.peaks(dev.device_kind) if require_tpu
                            else None)
    else:
        summary = None
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # The program's state goes before the reference runs, so the reference
    # neither sets the memory peak nor runs short of memory.
    system.free(sys_)
    numbers = {
        "logit_gap": check.logit_gap(
            ref, sys_.params, sys_.hp,
            check.sample(win, seed, params["sample_tokens"])),
        "heft_mismatch": check.heft_mismatches(run_.rec.decisions,
                                               run_.avail0),
        "misrouted": check.misrouted(run_.rec.decisions, run_.reqs),
        "malformed": check.malformed(win, sys_.hp["v"]),
    }
    limits = {"logit_gap": params["limits"]["logit_gap"], "heft_mismatch": 0,
              "misrouted": 0, "malformed": 0}

    result = {
        "correct": check.verdict(numbers, limits),
        "attempted": len(win),
        "failed": numbers["malformed"],
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs), "memory_peak_bytes": peak},
    }
    if trace and summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = check.report(numbers, limits)
    for k, v in result["checks"].items():
        say(f"{k} {v['value']} limit {v['limit']}")
    print(json.dumps(result), file=out, flush=True)
    return 0


def process_start_perf() -> float:
    """When this process started, on ``time.perf_counter``'s clock."""
    import psutil

    now_perf, now_wall = time.perf_counter(), time.time()
    return now_perf - (now_wall - psutil.Process().create_time())
