"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device metrics.

The trace holds, per chip, a ``/device:TPU:<n>`` plane whose ``XLA
Modules`` line has one event per executable run (``jit_<function>(<id>)``)
and whose ``XLA Ops`` line has one event per operation; and a ``/host:CPU``
plane holding the harness's annotations (``bench.window`` around the
profiled sub-window, ``engine.admit``, ``engine.decode_tick`` and
``fabric.map_event`` around the program's calls).

Device and host timestamps are not on one clock (they differ by a
millisecond or two on a v5e), so the reduction first finds the offset that
puts each decode-tick executable inside a ``engine.decode_tick``
annotation: the tick call blocks until its tokens are on the host, so its
device work lies inside the call.

From that: the busy time (the union of operation intervals inside the
window, averaged over chips), the device time of each annotated decode
tick, the device time per executable and per operation (its own time: an
operation that holds others, such as a loop, less theirs), and the longest
idle gaps (no chip busy), each labelled with the host annotation that
covers it, or ``none`` where the host was in none of them.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field

SPAN_NAMES = ("engine.admit", "engine.decode_tick", "fabric.map_event")
TICK_FNS = ("tick", "tick_sched", "tick_sched_counted")
WINDOW = "bench.window"


def module_name(event_name: str) -> str:
    """``jit_tick_sched_counted(8323...)`` -> ``tick_sched_counted``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events) -> list[float]:
    """Each event's duration less that of the events nested inside it
    (a loop's op spans the ops of its body on the same line)."""
    out = [e - s for s, e, _ in events]
    stack: list[int] = []
    for i, (s, e, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            out[stack[-1]] -= e - s
        stack.append(i)
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclass
class Device:
    modules: list           # (start_ns, end_ns, short name), by start
    ops: list               # (start_ns, end_ns, op name), by start


@dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over chips
    offset_ns: float                    # host time = device time + offset
    tick_device_s: list = field(default_factory=list)  # per annotated tick
    module_s: dict = field(default_factory=dict)       # mean over chips
    device_ops: list = field(default_factory=list)     # [[name, s]] top 10
    idle_gaps: list = field(default_factory=list)      # [[label, s]] top 10


def _outer_first(event):
    """Sort key: by start, the outer of two events that start together
    first."""
    return event[0], -event[1]


def load(path: str, n_devices: int | None = None):
    """(devices, host annotations) from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(e.start_ns, e.start_ns + e.duration_ns,
                             module_name(e.name)) for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns,
                            op_name(e.name)) for e in line.events]
            devices[idx] = Device(sorted(mods, key=_outer_first),
                                  sorted(ops, key=_outer_first))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPAN_NAMES or e.name == WINDOW:
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    if not devices:
        raise ValueError(f"{path}: no /device:TPU plane")
    ids = sorted(devices)[:n_devices]
    return [devices[i] for i in ids], sorted(host)


def align(dev: Device, ticks_host: list) -> float:
    """Offset (ns) that puts each tick executable inside its own tick
    annotation: the middle of the range every pair allows.  Pairs go in
    order where the counts agree, else by the offset that fits the most
    annotations one to one."""
    ticks_dev = [(s, e) for s, e, n in dev.modules if n in TICK_FNS]
    if not ticks_dev or not ticks_host:
        return 0.0
    if len(ticks_dev) == len(ticks_host):
        pairs = list(zip(ticks_dev, ticks_host))
    else:
        starts = [s for s, _ in ticks_host]

        def matched(delta):
            seen = {}
            for s, e in ticks_dev:
                j = bisect.bisect_right(starts, s + delta) - 1
                if j >= 0 and e + delta <= ticks_host[j][1]:
                    seen.setdefault(j, (s, e))
            return [(d, ticks_host[j]) for j, d in seen.items()]

        cands = sorted({((hs - s) + (he - e)) / 2
                        for s, e in ticks_dev[:20] for hs, he in ticks_host
                        if abs(he - e) < 1e8})
        pairs = matched(max(cands, key=lambda d: len(matched(d)),
                            default=0.0))
        if not pairs:
            return 0.0
    lo = max(hs - s for (s, _), (hs, _) in pairs)
    hi = min(he - e for (_, e), (_, he) in pairs)
    if lo <= hi:
        return (lo + hi) / 2
    mids = sorted(((hs - s) + (he - e)) / 2 for (s, e), (hs, he) in pairs)
    return mids[len(mids) // 2]


def reduce(path: str, n_devices: int | None = None) -> Summary:
    devices, host = load(path, n_devices)
    ticks_host = [(s, e) for s, e, n in host if n == "engine.decode_tick"]
    offset = align(devices[0], ticks_host)
    win = [(s, e) for s, e, n in host if n == WINDOW]
    if win:
        lo, hi = win[0][0] - offset, win[0][1] - offset
    else:
        lo = min(d.ops[0][0] for d in devices if d.ops)
        hi = max(d.ops[-1][1] for d in devices if d.ops)
    n = len(devices)

    busy, all_busy = 0.0, []
    ticks = [0.0] * len(ticks_host)
    tstarts = [hs for hs, _ in ticks_host]
    module_s: dict[str, float] = {}
    op_s: dict[str, float] = {}
    for d in devices:
        iv = union(clip([(s, e) for s, e, _ in d.ops], lo, hi))
        busy += sum(e - s for s, e in iv)
        all_busy += iv
        mstarts = [s for s, _, _ in d.modules]
        for s, e, name in d.modules:
            c = clip([(s, e)], lo, hi)
            if c:
                module_s[name] = module_s.get(name, 0.0) + (c[0][1] - c[0][0])
            if name in TICK_FNS:
                k = bisect.bisect_right(tstarts, s + offset) - 1
                if k >= 0 and e + offset <= ticks_host[k][1]:
                    ticks[k] += e - s
        for (s, e, name), own in zip(d.ops, self_times(d.ops)):
            if not lo <= s < hi:
                continue
            j = bisect.bisect_right(mstarts, s) - 1
            mod = d.modules[j][2] if j >= 0 and s < d.modules[j][1] else "?"
            key = f"{mod}:{name}"
            op_s[key] = op_s.get(key, 0.0) + own

    merged = union(all_busy)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    idle = sorted(((b - a, a) for a, b in zip(edges[0::2], edges[1::2])
                   if b > a), reverse=True)[:10]
    spans = [(s, e, nm) for s, e, nm in host if nm in SPAN_NAMES]
    gaps = []
    for length, a in idle:
        mid = a + length / 2 + offset
        label = next((nm for s, e, nm in spans if s <= mid <= e), "none")
        gaps.append([label, length / 1e9])
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy / n / 1e9,
        offset_ns=offset,
        tick_device_s=[t / n / 1e9 for t in ticks],
        module_s={k: v / n / 1e9 for k, v in module_s.items()},
        device_ops=[[k, v / n / 1e9] for k, v in top_ops],
        idle_gaps=gaps,
    )
