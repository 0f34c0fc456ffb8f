"""Trip-weighted HLO analysis for the roofline (§Roofline methodology).

XLA's ``compiled.cost_analysis()`` counts a while-loop body ONCE regardless of
trip count (verified empirically — a scan of 10 matmuls reports the FLOPs of
one), so per-op metrics must be weighted by execution counts.  All loops in
this codebase lower from ``lax.scan``/static ``fori_loop``, so every while
condition compares the induction variable against a CONSTANT bound that we can
parse from the HLO text.

The analyzer:
  1. splits the partitioned module into computations;
  2. builds the call graph (while body/condition, fusion/call `calls=`,
     conditional branches);
  3. assigns each computation an execution count = Σ over callers of
     caller_count × (trip count for while bodies, 1 otherwise);
  4. counts, with weights:
       * dot FLOPs: 2 × prod(output dims) × prod(lhs contracting dims),
       * dot memory traffic: operand + result bytes (the matmul-stream
         proxy for the roofline memory term),
       * collective wire bytes by op kind (all-gather / all-reduce /
         reduce-scatter / all-to-all / collective-permute).

Shapes in the partitioned module are PER-DEVICE, so all outputs are
per-device quantities.
"""

from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
                "c128": 16}

_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{")
_WHILE = re.compile(r"while\(.*?\)"
                    r".*?condition=%?([\w\.\-]+), body=%?([\w\.\-]+)")
_CALLS = re.compile(r"calls=%?([\w\.\-]+)")
_COND_BRANCH = re.compile(r"(?:true_computation|false_computation|"
                          r"branch_computations=\{)[^,}]*%?([\w\.\-]+)")
_CONST_BOUND = re.compile(r"s32\[\]\S*\s+constant\((\d+)\)")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_DOT = re.compile(r"=\s+(\w+)\[([\d,]*)\]\S*\s+dot\((.*?)\),")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_COLL = re.compile(
    r"=\s+(?:\(([^)]*)\)|(\w+\[[\d,]*\]))\S*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")

_WIRE_FACTOR = {"all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def _bytes_of(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _split_computations(hlo: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        m = _COMP_HDR.match(line.strip()) if "{" in line else None
        if m and ("->" in line):
            cur = m.group(1)
            comps[cur] = []
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
                continue
            comps[cur].append(line)
    return comps


def _trip_count(cond_lines: list[str]) -> int | None:
    bounds = []
    for line in cond_lines:
        for m in _CONST_BOUND.finditer(line):
            bounds.append(int(m.group(1)))
    return max(bounds) if bounds else None


def analyze_hlo(hlo: str, unknown_trip: int = 1) -> dict:
    comps = _split_computations(hlo)
    entry = None
    for line in hlo.splitlines():
        if line.strip().startswith("ENTRY"):
            m = _COMP_HDR.match(line.strip())
            if m:
                entry = m.group(1)
    if entry is None:  # fall back: biggest computation
        entry = max(comps, key=lambda k: len(comps[k]))

    # call edges: (caller, callee, multiplier)
    edges: dict[str, list[tuple[str, float]]] = defaultdict(list)
    unknown_trips = 0
    for name, lines in comps.items():
        for line in lines:
            mw = _WHILE.search(line)
            if mw:
                cond, body = mw.group(1), mw.group(2)
                trip = _trip_count(comps.get(cond, []))
                if trip is None:
                    trip = unknown_trip
                    unknown_trips += 1
                edges[name].append((body, float(max(trip, 1))))
                edges[name].append((cond, float(max(trip, 1))))
                continue
            mc = _CALLS.search(line)
            if mc and mc.group(1) in comps:
                edges[name].append((mc.group(1), 1.0))
            for mb in _COND_BRANCH.finditer(line):
                if mb.group(1) in comps:
                    edges[name].append((mb.group(1), 1.0))

    # propagate execution counts (the call graph is a DAG): repeated
    # relaxation, which settles within one pass per computation
    count: dict[str, float] = defaultdict(float)
    count[entry] = 1.0
    for _ in range(len(comps)):
        new = defaultdict(float)
        for caller, callees in edges.items():
            if count.get(caller, 0) <= 0:
                continue
            for callee, mult in callees:
                new[callee] += count[caller] * mult
        new[entry] = 1.0
        if dict(new) == dict(count):
            break
        count = new

    # definition map: op name → (dtype, dims); HLO op names are unique
    # module-wide in practice (suffix counters), so one global map suffices.
    defs: dict[str, tuple[str, str]] = {}
    _DEF = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\w+)\[([\d,]*)\]")
    for lines in comps.values():
        for line in lines:
            m = _DEF.match(line)
            if m:
                defs[m.group(1)] = (m.group(2), m.group(3))

    _OPERANDS = re.compile(r"%([\w\.\-]+)")
    flops = 0.0
    dot_bytes = 0.0
    coll_bytes: dict[str, float] = defaultdict(float)
    coll_count: dict[str, int] = defaultdict(int)
    for name, lines in comps.items():
        w = count.get(name, 0.0)
        if w <= 0:
            continue
        for line in lines:
            md = _DOT.search(line)
            if md:
                out_elems = 1
                for d in md.group(2).split(","):
                    if d:
                        out_elems *= int(d)
                op_names = _OPERANDS.findall(md.group(3))
                mc = _CONTRACT.search(line)
                k = 1
                if mc and op_names and op_names[0] in defs:
                    lhs_dims = [int(d) for d in defs[op_names[0]][1].split(",")
                                if d]
                    for ci in mc.group(1).split(","):
                        if ci:
                            k *= lhs_dims[int(ci)]
                flops += w * 2.0 * out_elems * k
                operand_bytes = sum(
                    _bytes_of(*defs[n]) for n in op_names[:2] if n in defs)
                dot_bytes += w * (_bytes_of(md.group(1), md.group(2))
                                  + operand_bytes)
                continue
            mcoll = _COLL.search(line)
            if mcoll:
                tuple_part, single, op = mcoll.groups()
                text = tuple_part if tuple_part else single
                size = sum(_bytes_of(dt, dd)
                           for dt, dd in _SHAPE.findall(text))
                coll_bytes[op] += w * size * _WIRE_FACTOR[op]
                coll_count[op] += 1

    return {
        "dot_flops_per_device": flops,
        "dot_bytes_per_device": dot_bytes,
        "collective_bytes_by_op": dict(coll_bytes),
        "collective_op_defs": dict(coll_count),
        "total_wire_bytes_per_device": sum(coll_bytes.values()),
        "num_computations": len(comps),
        "unknown_trip_whiles": unknown_trips,
    }


# ---------------------------------------------------------------------------
# unweighted collective inventory + compiled-step summary
# ---------------------------------------------------------------------------

def collective_stats(hlo_text: str) -> dict:
    """Unweighted collective inventory: wire bytes + op counts, body-once.

    The companion to :func:`analyze_hlo` (which trip-weights): one entry per
    collective *definition* in the partitioned module, using the same ring
    wire-byte conventions.  Import-light (pure regex) so tests and the
    serve cost-model can use it without the dry-run's XLA_FLAGS side
    effects.
    """
    per_op: dict[str, float] = {}
    count: dict[str, int] = {}
    for m in _COLL.finditer(hlo_text):
        tuple_part, single, op = m.group(1), m.group(2), m.group(3)
        text = tuple_part if tuple_part else single
        size = sum(_bytes_of(d, dims) for d, dims in _SHAPE.findall(text))
        per_op[op] = per_op.get(op, 0.0) + size * _WIRE_FACTOR[op]
        count[op] = count.get(op, 0) + 1
    return {"bytes_by_op": per_op,
            "count_by_op": count,
            "total_wire_bytes_per_device": sum(per_op.values())}


_MEM_FIELDS = ("temp_size_in_bytes", "argument_size_in_bytes",
               "output_size_in_bytes", "alias_size_in_bytes",
               "generated_code_size_in_bytes")


def summarize_compiled(compiled) -> dict:
    """Cost summary of one compiled step: XLA memory/cost analyses plus the
    collective inventory and trip-weighted roofline terms.

    The shared back-end of ``dryrun_cell`` and the tiny-mesh tests: the
    returned ``flops_per_device`` / ``bytes_accessed_per_device`` /
    ``collectives`` keys are exactly what
    :meth:`repro.sched_integration.cost_model.CostCell.from_dryrun` consumes.
    """
    mem = compiled.memory_analysis()
    mem_info = {k: getattr(mem, k, None) for k in _MEM_FIELDS}

    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    cost = cost or {}

    hlo = compiled.as_text()
    return {
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_accessed_per_device": float(cost.get("bytes accessed", 0.0)),
        "weighted": analyze_hlo(hlo),
        "collectives": collective_stats(hlo),
        "memory": mem_info,
        "hlo_chars": len(hlo),
    }
