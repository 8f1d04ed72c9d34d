#!/usr/bin/env python
"""spars-lint — repo-invariant static analysis for the SPARS reproduction.

The engine's reproducibility guarantees rest on hand-maintained invariants
(core/SEMANTICS.md §Design rules): every static ``EngineConfig`` field read
inside a jitted body must ride the jit-cache key, every ``PolicyParams``
flag must be branched on through ``static_bool``, every engine rule needs a
bit-exact pydes oracle twin, every Pallas wrapper needs a reference
fallback, and jit-traced bodies must stay pure. Two shipped bugs (the PR 5
rebuild-every-call recompile and the PR 6 cache-key-distinctness fix) were
exactly these invariants drifting; this tool machine-checks them as AST
passes so the next flag/const/kernel cannot break them silently.

Passes (each emits ``file:line RULE message``):

* **SL001 trace-key completeness** — every ``cfg.<attr>`` read inside the
  functions reachable from ``run_sim``/``run_sim_gantt`` (i.e. trace
  structure of the jitted program) appears in ``_static_trace_key``. A
  missed field silently reuses a program compiled for a different config
  (cache collision) or recompiles per call.
* **SL002 flag-gate discipline** — no raw ``pp.<flag>`` read of a
  ``PolicyParams`` field in a Python boolean context (``if``/``while``/
  ``assert``/``and``/``or``/``not``/ternary) in engine.py or policy.py:
  all must route through ``static_bool`` so the traced superset and the
  specialized DCE path stay the same program (§Static specialization).
* **SL003 oracle-twin coverage** — engine rule functions (first parameter
  ``s``) must map to a ``PyDES`` method by naming convention (modulo the
  documented alias and one-sided-by-design tables), and vice versa, so the
  two engines cannot drift one-sidedly.
* **SL004 kernel-contract** — every Pallas wrapper in ``kernels/ops.py``
  (a function calling a ``_*_kernel`` import) has a ``*_reference`` twin
  in ``kernels/ref.py``, a zero-size short-circuit, and a conditional
  untileable-fallback route to the reference.
* **SL005 tracer-leak / purity** — no ``np.``/``print``/``warnings`` host
  calls, no host span or clock (``spans.*``, ``TraceAnnotation``,
  ``time.perf_counter``) and no ``bool()``/``int()``/``float()``/
  ``.item()`` coercion of traced values (``s.*`` / ``const.*``) inside
  jit-traced bodies.
* **SL006 metrics-row consistency** — every ``SimMetrics`` field is
  consumed by ``row()`` (transitively through its helper methods), so a
  gated field cannot ship without its gated column.
* **SL007 docs hygiene** — the former ``tools/docs_check.py``
  (``docs_pass.py``): dead links, stale file refs, fence balance, fenced
  command resolution.

Waive an intentional violation with ``# spars-lint: ignore[SLxxx] <reason>``
on the flagged line, or anywhere in the contiguous comment block directly
above it. Run as ``make lint`` (all passes), ``make docs-check``
(``--only SL007``), or in tier-1 via ``tests/test_lint.py``.
"""
from __future__ import annotations

import argparse
import ast
import os
import re
import sys
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Set, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import docs_pass  # noqa: E402

REPO = docs_pass.REPO

# repo-relative locations of the checked files; a fixture tree (tests/
# fixtures/lint/<case>/) overrides the root and provides only the files its
# rule needs — a pass whose files are absent is skipped for that root
ENGINE = "src/repro/core/engine.py"
POLICY = "src/repro/core/policy.py"
PYDES = "src/repro/core/ref/pydes.py"
TYPES = "src/repro/core/types.py"
OPS = "src/repro/kernels/ops.py"
KREF = "src/repro/kernels/ref.py"


class Finding(NamedTuple):
    file: str  # root-relative path
    line: int
    rule: str
    msg: str

    def render(self) -> str:
        return f"{self.file}:{self.line} {self.rule} {self.msg}"


# ---------------------------------------------------------------------------
# shared plumbing
# ---------------------------------------------------------------------------

_IGNORE = re.compile(r"#\s*spars-lint:\s*ignore\[([A-Z0-9, ]+)\]")
_COMMENT_ONLY = re.compile(r"^\s*#")


class _File:
    """Parsed source + waiver lookup for one file."""

    def __init__(self, root: str, rel: str):
        self.rel = rel
        self.path = os.path.join(root, rel)
        with open(self.path) as f:
            self.source = f.read()
        self.lines = self.source.splitlines()
        self.tree = ast.parse(self.source, filename=rel)

    def waived(self, line: int, rule: str) -> bool:
        """True if ``line`` (1-based) or the contiguous comment block
        directly above it carries ``# spars-lint: ignore[rule]``."""
        i = line - 1
        if 0 <= i < len(self.lines) and self._tagged(self.lines[i], rule):
            return True
        i -= 1
        while i >= 0 and _COMMENT_ONLY.match(self.lines[i]):
            if self._tagged(self.lines[i], rule):
                return True
            i -= 1
        return False

    @staticmethod
    def _tagged(text: str, rule: str) -> bool:
        m = _IGNORE.search(text)
        return bool(m) and rule in [r.strip() for r in m.group(1).split(",")]


def _load(root: str, rel: str) -> Optional[_File]:
    if not os.path.exists(os.path.join(root, rel)):
        return None
    return _File(root, rel)


def _top_functions(tree: ast.Module) -> Dict[str, ast.FunctionDef]:
    return {
        n.name: n
        for n in tree.body
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }


def _class_methods(tree: ast.Module, cls: str) -> Dict[str, ast.FunctionDef]:
    for n in tree.body:
        if isinstance(n, ast.ClassDef) and n.name == cls:
            return {
                m.name: m
                for m in n.body
                if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef))
            }
    return {}


def _called_names(node: ast.AST) -> Set[str]:
    """Names invoked as plain calls anywhere under ``node`` (incl. nested
    defs/lambdas — lax.while_loop bodies are nested functions)."""
    return {
        n.func.id
        for n in ast.walk(node)
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
    }


def _mentions(node: ast.AST, names: Set[str]) -> bool:
    return any(
        isinstance(n, ast.Name) and n.id in names for n in ast.walk(node)
    )


def _attr_names(node: ast.AST) -> Set[str]:
    return {n.attr for n in ast.walk(node) if isinstance(n, ast.Attribute)}


_CFG_NAMES = {"cfg", "config"}


def _cfg_reads(fn: ast.AST) -> List[Tuple[str, int]]:
    """Dotted config-attribute paths read under ``fn``.

    ``cfg.window`` -> ``window``; ``cfg.policy.dvfs`` and
    ``getattr(cfg.policy, "dvfs", ...)`` -> ``policy.dvfs`` (the bare
    ``policy`` base is consumed by the compound read).
    """
    reads: List[Tuple[str, int]] = []
    consumed: Set[int] = set()

    def is_cfg_attr(n: ast.AST) -> bool:
        return (
            isinstance(n, ast.Attribute)
            and isinstance(n.value, ast.Name)
            and n.value.id in _CFG_NAMES
        )

    for n in ast.walk(fn):
        # getattr(cfg.X, "Y", ...) -> "X.Y"
        if (
            isinstance(n, ast.Call)
            and isinstance(n.func, ast.Name)
            and n.func.id == "getattr"
            and n.args
            and is_cfg_attr(n.args[0])
            and len(n.args) >= 2
            and isinstance(n.args[1], ast.Constant)
            and isinstance(n.args[1].value, str)
        ):
            reads.append(
                (f"{n.args[0].attr}.{n.args[1].value}", n.lineno)
            )
            consumed.add(id(n.args[0]))
        # cfg.X.Y -> "X.Y"
        elif isinstance(n, ast.Attribute) and is_cfg_attr(n.value):
            reads.append((f"{n.value.attr}.{n.attr}", n.lineno))
            consumed.add(id(n.value))
    for n in ast.walk(fn):
        if is_cfg_attr(n) and id(n) not in consumed:
            reads.append((n.attr, n.lineno))
    return reads


def _cfg_call_args(fn: ast.AST) -> Set[str]:
    """Module-level function names that ``fn`` calls with the config object
    as an argument (``_fused_kernel_on(config)`` — their own cfg reads are
    part of the caller's trace structure)."""
    out: Set[str] = set()
    for n in ast.walk(fn):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name):
            for a in n.args:
                if isinstance(a, ast.Name) and a.id in _CFG_NAMES:
                    out.add(n.func.id)
    return out


def _reachable(
    funcs: Dict[str, ast.FunctionDef], roots: Iterable[str]
) -> Set[str]:
    seen: Set[str] = set()
    todo = [r for r in roots if r in funcs]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        todo.extend(c for c in _called_names(funcs[name]) if c in funcs)
    return seen


# ---------------------------------------------------------------------------
# SL001 — trace-key completeness
# ---------------------------------------------------------------------------

TRACE_ROOTS = ("run_sim", "run_sim_gantt")
KEY_FN = "_static_trace_key"


def check_sl001(root: str) -> List[Finding]:
    f = _load(root, ENGINE)
    if f is None:
        return []
    funcs = _top_functions(f.tree)
    key_fn = funcs.get(KEY_FN)
    if key_fn is None:
        return [
            Finding(f.rel, 1, "SL001",
                    f"jit cache key function {KEY_FN}() not found")
        ]
    covered = {p for p, _ in _cfg_reads(key_fn)}
    # a helper called with the config object inside the key contributes its
    # own static reads to the key (e.g. _fused_kernel_on(config))
    for helper in _cfg_call_args(key_fn):
        if helper in funcs:
            covered |= {p for p, _ in _cfg_reads(funcs[helper])}

    out: List[Finding] = []
    for name in sorted(_reachable(funcs, TRACE_ROOTS)):
        for path, line in _cfg_reads(funcs[name]):
            if path in covered:
                continue
            # a compound read (policy.controller) also covers checks that
            # re-read its exact dotted path; a bare base read is only
            # covered by a bare entry
            if f.waived(line, "SL001"):
                continue
            out.append(Finding(
                f.rel, line, "SL001",
                f"static config read `cfg.{path}` in jitted scope "
                f"({name}) is missing from {KEY_FN} — cache collisions "
                "or per-call recompiles",
            ))
    return out


# ---------------------------------------------------------------------------
# SL002 — flag-gate discipline
# ---------------------------------------------------------------------------

# fallback when the checked tree does not carry policy.py (fixture roots);
# the live run parses PolicyParams so new flags are picked up automatically
DEFAULT_FLAGS = (
    "backfill", "eager_ready", "sleep_enabled", "ipm_enabled",
    "rl_enabled", "rl_grouped", "dvfs_enabled", "dvfs_rl",
    "forecast_enabled", "forecast_dvfs",
)

STATIC_ACCESSOR = "static_bool"


def _policy_flags(root: str) -> Tuple[str, ...]:
    f = _load(root, POLICY)
    if f is None:
        return DEFAULT_FLAGS
    for n in f.tree.body:
        if isinstance(n, ast.ClassDef) and n.name == "PolicyParams":
            fields = tuple(
                stmt.target.id
                for stmt in n.body
                if isinstance(stmt, ast.AnnAssign)
                and isinstance(stmt.target, ast.Name)
            )
            if fields:
                return fields
    return DEFAULT_FLAGS


def _gate_exprs(tree: ast.AST) -> List[ast.AST]:
    """Expressions evaluated in a Python boolean context."""
    out: List[ast.AST] = []
    for n in ast.walk(tree):
        if isinstance(n, (ast.If, ast.While, ast.IfExp)):
            out.append(n.test)
        elif isinstance(n, ast.Assert):
            out.append(n.test)
        elif isinstance(n, ast.BoolOp):
            out.extend(n.values)
        elif isinstance(n, ast.UnaryOp) and isinstance(n.op, ast.Not):
            out.append(n.operand)
    return out


def _raw_flag_reads(
    expr: ast.AST, flags: Set[str]
) -> List[ast.Attribute]:
    """Flag attribute reads under ``expr`` not wrapped in static_bool()."""
    hits: List[ast.Attribute] = []

    def visit(node: ast.AST, shielded: bool) -> None:
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == STATIC_ACCESSOR
        ):
            shielded = True
        if (
            isinstance(node, ast.Attribute)
            and node.attr in flags
            and not shielded
        ):
            hits.append(node)
        for child in ast.iter_child_nodes(node):
            visit(child, shielded)

    visit(expr, False)
    return hits


def check_sl002(root: str) -> List[Finding]:
    flags = set(_policy_flags(root))
    out: List[Finding] = []
    for rel in (ENGINE, POLICY):
        f = _load(root, rel)
        if f is None:
            continue
        for expr in _gate_exprs(f.tree):
            for hit in _raw_flag_reads(expr, flags):
                if f.waived(hit.lineno, "SL002"):
                    continue
                out.append(Finding(
                    f.rel, hit.lineno, "SL002",
                    f"raw PolicyParams flag `.{hit.attr}` in a Python "
                    f"boolean gate — route through {STATIC_ACCESSOR}() so "
                    "traced sweeps and specialized DCE stay one program",
                ))
    return out


# ---------------------------------------------------------------------------
# SL003 — oracle-twin coverage
# ---------------------------------------------------------------------------

# engine rule name -> PyDES method name, where the convention (strip
# leading underscores, equal names) does not hold for historical reasons
SL003_ALIASES = {
    "_complete_jobs": "_complete",
    "_complete_transitions": "_transitions",
    "_ready_times": "_ready",
    "accrue_energy": "_accrue",
    "apply_rl_commands": "_apply_rl",
    "run_sim": "run",
}

# engine-side rule functions with no oracle twin BY DESIGN (vectorization
# artifacts of rules that are twinned at a coarser granularity); every
# entry names its justification so additions are a conscious act
SL003_ENGINE_ONLY = {
    "_queue_window": "window scatter spelling of _scheduler_pass's queue slice",
    "_sched_attempt": "loop-body factoring shared by both scheduler loops",
    "_power_step": "rules 6-9 dispatcher; the oracle inlines it in _process_batch",
    "_time_candidates": "folded into the oracle's _next_time",
    "_next_transition": "folded into the oracle's _next_time",
    "_node_power_draw": "inlined in the oracle's _accrue",
    "event_horizon": "fused next_time+draw spelling (§Hot loop); parity-tested",
    "_quiet_batch": "proven-no-op fast path; the oracle has no quiet dispatch",
    "all_done": "inlined in the oracle's run loop",
    "run_sim_gantt": "gantt-recording variant of run_sim",
}

# oracle-side methods with no s-first engine twin BY DESIGN
SL003_ORACLE_ONLY = {
    "__init__": "constructor",
    "_partition_select": "host spelling of the engine's _partition_pick "
                         "per-group masked cumsum inside _try_allocate",
    "energy_by_state": "legacy view summed from energy_by_group",
    "_eff_speed": "twin is policy.effective_node_speed (const-first signature)",
    "_sort_key": "host spelling of the engine's (ready, order_key, nid) argsort",
    "_gantt_mark": "oracle-side gantt recorder; engine twin is run_sim_gantt's log",
    "_eligible": "inlined in the engine as the `node_job < 0` mask",
    "metrics": "engine twin is metrics.metrics_from_state (host-side module)",
    "schedule_table": "engine twin is metrics.schedule_table (host-side module)",
}


def _norm(name: str) -> str:
    return name.lstrip("_")


def check_sl003(root: str) -> List[Finding]:
    pydes = _load(root, PYDES)
    engine = _load(root, ENGINE)
    if pydes is None or engine is None:
        return []
    oracle = _class_methods(pydes.tree, "PyDES")
    candidates: List[Tuple[_File, ast.FunctionDef]] = []
    for rel in (ENGINE, POLICY):
        f = _load(root, rel)
        if f is None:
            continue
        for fn in _top_functions(f.tree).values():
            args = fn.args.args
            if args and args[0].arg == "s":
                candidates.append((f, fn))

    out: List[Finding] = []
    engine_targets: Set[str] = set()
    for f, fn in candidates:
        target = SL003_ALIASES.get(fn.name, fn.name)
        engine_targets.add(_norm(target))
        if fn.name in SL003_ENGINE_ONLY:
            continue
        if any(_norm(m) == _norm(target) for m in oracle):
            continue
        if f.waived(fn.lineno, "SL003"):
            continue
        out.append(Finding(
            f.rel, fn.lineno, "SL003",
            f"engine rule `{fn.name}` has no pydes oracle twin "
            f"(expected PyDES.{target} or an alias/engine-only entry in "
            "spars_lint.SL003_*) — engine/oracle drift",
        ))
    for name, m in oracle.items():
        if name in SL003_ORACLE_ONLY or _norm(name) in engine_targets:
            continue
        if pydes.waived(m.lineno, "SL003"):
            continue
        out.append(Finding(
            pydes.rel, m.lineno, "SL003",
            f"oracle method `PyDES.{name}` has no engine rule twin "
            "(expected a matching s-first function or an alias/oracle-only "
            "entry in spars_lint.SL003_*) — engine/oracle drift",
        ))
    return out


# ---------------------------------------------------------------------------
# SL004 — Pallas kernel-wrapper contract
# ---------------------------------------------------------------------------

_KERNEL_NAME = re.compile(r"^_\w*kernel$")


def _ref_calls(fn: ast.FunctionDef) -> List[ast.Call]:
    """Calls to ``ref.<x>_reference`` under ``fn``."""
    return [
        n
        for n in ast.walk(fn)
        if isinstance(n, ast.Call)
        and isinstance(n.func, ast.Attribute)
        and isinstance(n.func.value, ast.Name)
        and n.func.value.id == "ref"
        and n.func.attr.endswith("_reference")
    ]


def _has_zero_size_guard(fn: ast.FunctionDef) -> bool:
    """An If whose test compares against 0 (``e == 0`` / ``0 in shape``)
    and whose body returns — the zero-size short-circuit."""
    for n in ast.walk(fn):
        if not isinstance(n, ast.If):
            continue
        zeroish = any(
            isinstance(c, ast.Compare)
            and any(isinstance(op, (ast.Eq, ast.In)) for op in c.ops)
            and any(
                isinstance(x, ast.Constant) and x.value == 0
                for x in [c.left] + list(c.comparators)
            )
            for c in ast.walk(n.test)
        )
        if zeroish and any(
            isinstance(b, ast.Return) for b in ast.walk(n)
        ):
            return True
    return False


def _conditional_ref_route(fn: ast.FunctionDef) -> bool:
    """At least one ref.*_reference call lives under an If (the
    untileable-shape fallback), not as the unconditional body."""
    for n in ast.walk(fn):
        if isinstance(n, ast.If):
            if any(_ref_calls_in(n)):
                return True
    return False


def _ref_calls_in(node: ast.AST) -> List[ast.Call]:
    return [
        c
        for c in ast.walk(node)
        if isinstance(c, ast.Call)
        and isinstance(c.func, ast.Attribute)
        and isinstance(c.func.value, ast.Name)
        and c.func.value.id == "ref"
        and c.func.attr.endswith("_reference")
    ]


def check_sl004(root: str) -> List[Finding]:
    ops = _load(root, OPS)
    if ops is None:
        return []
    kref = _load(root, KREF)
    ref_defs = set(_top_functions(kref.tree)) if kref else set()

    out: List[Finding] = []
    for fn in _top_functions(ops.tree).values():
        calls_kernel = any(
            _KERNEL_NAME.match(c) for c in _called_names(fn)
        )
        if not calls_kernel:
            continue
        waived = ops.waived(fn.lineno, "SL004")
        refs = _ref_calls(fn)
        if not refs:
            if not waived:
                out.append(Finding(
                    ops.rel, fn.lineno, "SL004",
                    f"kernel wrapper `{fn.name}` never routes to a "
                    "ref.*_reference twin — untileable shapes have no "
                    "fallback",
                ))
        else:
            for call in refs:
                if kref is not None and call.func.attr not in ref_defs:
                    out.append(Finding(
                        ops.rel, call.lineno, "SL004",
                        f"kernel wrapper `{fn.name}` falls back to "
                        f"ref.{call.func.attr}, which does not exist in "
                        f"{KREF}",
                    ))
            if not _conditional_ref_route(fn) and not waived:
                out.append(Finding(
                    ops.rel, fn.lineno, "SL004",
                    f"kernel wrapper `{fn.name}`'s reference route is "
                    "unconditional — the kernel path is dead",
                ))
        if not _has_zero_size_guard(fn) and not waived:
            out.append(Finding(
                ops.rel, fn.lineno, "SL004",
                f"kernel wrapper `{fn.name}` has no zero-size "
                "short-circuit (`== 0` / `0 in shape` guard returning "
                "early) — empty operands reach the kernel/reference",
            ))
    return out


# ---------------------------------------------------------------------------
# SL005 — tracer-leak / purity of jit-traced bodies
# ---------------------------------------------------------------------------

_TRACED_VARS = {"s", "const", "state"}
_HOST_COERCIONS = {"bool", "int", "float"}
_HOST_METHODS = {"item", "tolist"}
# host spans and clocks: inside a traced body they run once, at trace time
_HOST_CLOCKS = {"perf_counter", "TraceAnnotation"}


def _traced_scope(root: str) -> List[Tuple[_File, ast.FunctionDef]]:
    """The jit-traced function set: engine functions reachable from the run
    drivers, plus the s-first rule functions of policy.py."""
    out: List[Tuple[_File, ast.FunctionDef]] = []
    engine = _load(root, ENGINE)
    if engine is not None:
        funcs = _top_functions(engine.tree)
        for name in sorted(_reachable(funcs, TRACE_ROOTS)):
            out.append((engine, funcs[name]))
    policy = _load(root, POLICY)
    if policy is not None:
        for fn in _top_functions(policy.tree).values():
            if fn.args.args and fn.args.args[0].arg == "s":
                out.append((policy, fn))
    return out


def _host_span_finding(what: str, fn: str) -> str:
    return (
        f"host span or clock `{what}` inside jit-traced body `{fn}` — it "
        "runs once, at trace time, and times nothing on the device; use "
        "jax.named_scope there, and spans around the host-side caller"
    )


def check_sl005(root: str) -> List[Finding]:
    out: List[Finding] = []
    for f, fn in _traced_scope(root):
        for n in ast.walk(fn):
            finding = None
            if isinstance(n, ast.Attribute) and isinstance(n.value, ast.Name):
                if n.value.id == "np":
                    finding = (
                        f"host numpy call `np.{n.attr}` inside jit-traced "
                        f"body `{fn.name}` — use jnp (np breaks tracing "
                        "and silently constant-folds)"
                    )
                elif n.value.id == "warnings":
                    finding = (
                        f"host side effect `warnings.{n.attr}` inside "
                        f"jit-traced body `{fn.name}` — warn from the "
                        "host driver instead"
                    )
                elif n.value.id == "spans" or n.attr in _HOST_CLOCKS:
                    finding = _host_span_finding(
                        f"{n.value.id}.{n.attr}", fn.name)
            elif isinstance(n, ast.Attribute) and n.attr in _HOST_CLOCKS:
                finding = _host_span_finding(n.attr, fn.name)
            elif isinstance(n, ast.Name) and n.id in _HOST_CLOCKS:
                finding = _host_span_finding(n.id, fn.name)
            elif isinstance(n, ast.Call) and isinstance(n.func, ast.Name):
                if n.func.id == "print":
                    finding = (
                        f"print() inside jit-traced body `{fn.name}` — "
                        "use jax.debug.print or log from the host"
                    )
                elif (
                    n.func.id in _HOST_COERCIONS
                    and n.args
                    and _mentions(n.args[0], _TRACED_VARS)
                    and "shape" not in _attr_names(n.args[0])
                ):
                    finding = (
                        f"{n.func.id}() on a traced value inside "
                        f"`{fn.name}` — a Python coercion of a tracer "
                        "raises ConcretizationTypeError (or silently "
                        "freezes the value at trace time)"
                    )
            elif (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr in _HOST_METHODS
            ):
                finding = (
                    f".{n.func.attr}() inside jit-traced body "
                    f"`{fn.name}` — host materialization of a traced value"
                )
            if finding is None:
                continue
            if f.waived(n.lineno, "SL005"):
                continue
            out.append(Finding(f.rel, n.lineno, "SL005", finding))
    return out


# ---------------------------------------------------------------------------
# SL006 — SimMetrics field / row() column consistency
# ---------------------------------------------------------------------------

METRICS_CLASS = "SimMetrics"
ROW_FN = "row"


def check_sl006(root: str) -> List[Finding]:
    f = _load(root, TYPES)
    if f is None:
        return []
    cls = next(
        (
            n
            for n in f.tree.body
            if isinstance(n, ast.ClassDef) and n.name == METRICS_CLASS
        ),
        None,
    )
    if cls is None:
        return []
    fields = [
        (stmt.target.id, stmt.lineno)
        for stmt in cls.body
        if isinstance(stmt, ast.AnnAssign) and isinstance(stmt.target, ast.Name)
    ]
    methods = {
        m.name: m
        for m in cls.body
        if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    if ROW_FN not in methods:
        return [
            Finding(f.rel, cls.lineno, "SL006",
                    f"{METRICS_CLASS} has no {ROW_FN}() method")
        ]

    # self.<attr> reads in row(), transitively through self.method() calls
    used: Set[str] = set()
    seen: Set[str] = set()
    todo = [ROW_FN]
    while todo:
        name = todo.pop()
        if name in seen or name not in methods:
            continue
        seen.add(name)
        for n in ast.walk(methods[name]):
            if (
                isinstance(n, ast.Attribute)
                and isinstance(n.value, ast.Name)
                and n.value.id == "self"
            ):
                used.add(n.attr)
                if n.attr in methods:
                    todo.append(n.attr)

    out: List[Finding] = []
    for name, line in fields:
        if name in used or f.waived(line, "SL006"):
            continue
        out.append(Finding(
            f.rel, line, "SL006",
            f"{METRICS_CLASS} field `{name}` never reaches {ROW_FN}() — "
            "a gated metric without its gated column (or dead weight)",
        ))
    return out


# ---------------------------------------------------------------------------
# SL007 — docs hygiene (tools/lint/docs_pass.py)
# ---------------------------------------------------------------------------

def check_sl007(root: str) -> List[Finding]:
    out: List[Finding] = []
    for problem in docs_pass.collect(root=root):
        doc, _, msg = problem.partition(": ")
        out.append(Finding(doc, 1, "SL007", msg or problem))
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

PASSES = (
    ("SL001", "trace-key completeness", check_sl001),
    ("SL002", "flag-gate discipline", check_sl002),
    ("SL003", "oracle-twin coverage", check_sl003),
    ("SL004", "kernel-wrapper contract", check_sl004),
    ("SL005", "tracer-leak / purity", check_sl005),
    ("SL006", "metrics-row consistency", check_sl006),
    ("SL007", "docs hygiene", check_sl007),
)

RULE_IDS = tuple(rule for rule, _, _ in PASSES)


def run_passes(
    root: str = REPO, only: Optional[Sequence[str]] = None
) -> List[Finding]:
    selected = set(only) if only else set(RULE_IDS)
    unknown = selected - set(RULE_IDS)
    if unknown:
        raise SystemExit(
            f"spars-lint: unknown rule(s) {sorted(unknown)}; "
            f"known: {', '.join(RULE_IDS)}"
        )
    findings: List[Finding] = []
    for rule, _, fn in PASSES:
        if rule in selected:
            findings.extend(fn(root))
    return sorted(findings)


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="spars-lint",
        description="repo-invariant static analysis (SL001-SL007)",
    )
    p.add_argument(
        "--root", default=REPO,
        help="tree to check (default: this repo; tests point it at "
        "seeded-violation fixtures)",
    )
    p.add_argument(
        "--only", default=None,
        help="comma-separated rule ids to run (e.g. SL001,SL004); "
        "default: all",
    )
    p.add_argument(
        "--list", action="store_true", help="list rules and exit"
    )
    args = p.parse_args(argv)
    if args.list:
        for rule, title, _ in PASSES:
            print(f"{rule}  {title}")
        return 0
    only = args.only.split(",") if args.only else None
    findings = run_passes(root=os.path.abspath(args.root), only=only)
    for x in findings:
        print(x.render(), file=sys.stderr)
    n_rules = len(only) if only else len(PASSES)
    if findings:
        print(
            f"spars-lint: {len(findings)} finding(s) "
            f"(waive intentional ones with `# spars-lint: ignore[SLxxx] "
            "<reason>`)",
            file=sys.stderr,
        )
        return 1
    print(f"spars-lint: {n_rules} pass(es) clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
