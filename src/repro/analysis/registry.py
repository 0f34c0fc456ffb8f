"""Rule registry + the analysis context rules read their configuration from.

Two rule scopes:

* ``file``  — ``fn(ctx, path, tree, lines) -> Iterable[Finding]``, called
  once per parsed source file.
* ``repo``  — ``fn(ctx) -> Iterable[Finding]``, called once per run; these
  rules cross files (site inventories, schema/validator pairs, docs).

Every repo-structure assumption lives on :class:`AnalysisContext` (hot
function registry, axis names, the paths of the hint inventory / event
module / launchers / knob docs), so the test suite can point the same rules
at fixture trees under ``tests/analysis_fixtures/``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Iterable

# Functions registered *hot*: the steady-state serving/training inner loops
# whose latency budget the ROADMAP 9 ns item is chased against.  Inside
# these, host round-trips are design-rule violations (rule
# host-sync-in-hot-path), not style nits.  Extend per-run with
# REPRO_LINT_HOT=name1,name2.
DEFAULT_HOT_FUNCTIONS = frozenset({
    "decode_tick",      # serve/paging.py + serve/engine.py per-tick decode
    "_run_tick",        # PagedRuntime.decode_tick's body over the live slots
    "decode_step",      # models/model.py traced decode
    "_decode",          # ServeEngine's jitted decode closure site
    "map_event",        # MappingFabric single-event dispatch
    "map_batch",        # MappingFabric batched dispatch
    "step",             # ServeEngine.step / train step bodies / scan steps
    "tick",             # PagedRuntime's jitted gather→decode→scatter body
    "schedule",         # HeftFrontEnd per-event mapping
    "tick_sched",           # fused tick: decode + in-program HEFT_RT decision
    "tick_sched_counted",   # fused tick variant with device counters
    "decision_ref",         # kernels/fused_decision traced decision body
    "tick_decision_inputs",  # fabric staging for the fused tick
    "commit_tick_decision",  # fabric adoption of fused-tick outputs
})

# The ROADMAP's three logical mesh axes — the only names a PartitionSpec
# literal outside dist/ may mention (rule sharding-axis).
DEFAULT_AXIS_NAMES = frozenset({"pod", "data", "model"})


@dataclass
class AnalysisContext:
    """Everything a rule needs to know about the tree under analysis."""

    root: Path                      # repo root (paths render relative to it)
    files: tuple[Path, ...]         # files file-scope rules run over
    hot_functions: frozenset = DEFAULT_HOT_FUNCTIONS
    axis_names: frozenset = DEFAULT_AXIS_NAMES
    # Path parts exempt from the sharding-axis rule (the distribution
    # substrate itself is where non-model axes are legitimately named).
    axis_exempt_parts: tuple = ("dist",)
    # Repo-scope rule anchors (None → that rule skips itself).
    hints_path: Path | None = None       # SITE_INVENTORY source
    models_dir: Path | None = None       # shard_hint call-site tree
    fleet_path: Path | None = None       # event dataclasses + validators
    launch_dir: Path | None = None       # argparse launchers
    knobs_md: Path | None = None         # docs/knobs.md
    _sources: dict = field(default_factory=dict)

    def relpath(self, path) -> str:
        p = Path(path).resolve()
        try:
            return p.relative_to(self.root).as_posix()
        except ValueError:
            return p.as_posix()

    def source_lines(self, path) -> list[str]:
        """Cached physical lines of ``path`` (for noqa + repo-scope rules)."""
        p = Path(path).resolve()
        if p not in self._sources:
            self._sources[p] = p.read_text().splitlines()
        return self._sources[p]


def _iter_py(paths) -> list[Path]:
    out = []
    for p in paths:
        p = Path(p)
        if p.is_dir():
            out.extend(sorted(q for q in p.rglob("*.py")
                              if "__pycache__" not in q.parts))
        elif p.suffix == ".py":
            out.append(p)
    return out


def default_context(root, paths=None, *,
                    hot_extra: Iterable[str] = ()) -> AnalysisContext:
    """The context for THIS repo's layout (``src/repro/...``).

    ``paths`` narrows which files the file-scope rules visit (default:
    ``<root>/src``); the repo-scope anchors always resolve against ``root``
    and drop to None when absent, so the same builder works on fixture
    trees.
    """
    root = Path(root).resolve()
    scan = [Path(p) for p in paths] if paths else [root / "src"]
    hot = set(DEFAULT_HOT_FUNCTIONS) | set(hot_extra)
    hot |= {h.strip() for h in os.environ.get("REPRO_LINT_HOT", "").split(",")
            if h.strip()}

    def opt(p: Path):
        return p if p.exists() else None

    return AnalysisContext(
        root=root,
        files=tuple(_iter_py(scan)),
        hot_functions=frozenset(hot),
        hints_path=opt(root / "src/repro/dist/hints.py"),
        models_dir=opt(root / "src/repro/models"),
        fleet_path=opt(root / "src/repro/sched_integration/fleet.py"),
        launch_dir=opt(root / "src/repro/launch"),
        knobs_md=opt(root / "docs/knobs.md"),
    )


@dataclass(frozen=True)
class Rule:
    name: str
    scope: str                      # "file" | "repo"
    doc: str
    fn: Callable


_RULES: dict[str, Rule] = {}


def rule(name: str, scope: str = "file"):
    """Register a rule under ``name`` (its docstring becomes the catalogue
    entry printed by ``--list-rules``)."""
    if scope not in ("file", "repo"):
        raise ValueError(f"rule scope must be file|repo, got {scope!r}")

    def deco(fn):
        if name in _RULES:
            raise ValueError(f"duplicate rule name {name!r}")
        _RULES[name] = Rule(name, scope, (fn.__doc__ or "").strip(), fn)
        return fn
    return deco


def all_rules() -> dict[str, Rule]:
    """The registry, with the built-in rule modules imported."""
    from repro.analysis import rules_ast, rules_repo  # noqa: F401
    return dict(_RULES)
