"""Plan-level static verifier: runs after plan, before (or without) jit.

Three analysis families over the Plan-IR (analysis/plan_ir.py), each
with stable codes in diagnostics.CATALOG:

  1. **Automaton verification** (PV001-PV005) — transition-table
     well-formedness (no dangling state ids), start-reachability,
     accept-liveness (a plan whose accept state is unreachable can
     never match — Hyperscan-style compile-time graph analysis),
     `within`-bound propagation against summed absent waits, and the
     liveness-pruning report (states deleted with match output proven
     unchanged).
  2. **Jaxpr kernel sanitizer** (PV010-PV013) — traces each jitted
     step to a jaxpr and scans it for host callbacks, float64 upcasts,
     data-dependent (untraceable) shapes, and gather/scatter in kernels
     that declare themselves elementwise.  The only pass that needs
     jax; imports it lazily so `python -m siddhi_tpu.analyze` keeps its
     no-jax guarantee (plan checks run behind `--plan`).
  3. **Static cost model** (PC001-PC003, analysis/cost_model.py) —
     HBM footprint and FLOP-per-event estimates with a budget gate.

Entry points:
  * :func:`verify_automaton` / :func:`sanitize_step` — unit-testable
    pieces;
  * :func:`verify_plan` — PlanIR (+ optional runtime for the jaxpr
    pass) -> :class:`PlanReport`;
  * :func:`attach_plan_analysis` — wires the report and its
    diagnostics into ``rt.analysis`` (create_siddhi_app_runtime calls
    this after the plan is built).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from .cost_model import CostReport, cost_diagnostics, plan_cost
from .diagnostics import Diagnostic, Severity
from .plan_ir import AutomatonIR, PlanIR, extract_plan

#: primitive names that round-trip to the host per step
_CALLBACK_PRIMS = {"pure_callback", "io_callback", "callback",
                   "debug_callback", "outside_call", "host_callback_call"}
#: lane-crossing addressing primitives (fine in the NFA/egress kernels,
#: a hazard in kernels that declare themselves elementwise)
_GATHER_PRIMS = {"gather", "scatter", "scatter-add", "scatter_add",
                 "scatter_max", "scatter_min", "scatter_mul"}


# =================================================== automaton verification

def verify_automaton(a: AutomatonIR) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    S = len(a.states)
    accept = a.accept

    # PV001 — dangling state ids in the transition table
    for (src, label, dst) in a.transitions:
        if not (0 <= src < S) or not (0 <= dst <= accept):
            diags.append(Diagnostic(
                "PV001",
                f"transition ({src} --{label}--> {dst}) references a "
                f"state outside [0, {accept}]", query=a.query))
    if any(d.code == "PV001" for d in diags):
        return diags        # graph algorithms below assume a sane table

    # forward reachability from the start states
    fwd: Dict[int, set] = {}
    for (src, _label, dst) in a.transitions:
        fwd.setdefault(src, set()).add(dst)
    seen = set()
    stack = [s for s in a.start_states if 0 <= s <= accept]
    while stack:
        n = stack.pop()
        if n in seen or n == accept:
            if n == accept:
                seen.add(n)
            continue
        seen.add(n)
        stack.extend(fwd.get(n, ()))
    for s in a.states:
        if s.idx not in seen:
            diags.append(Diagnostic(
                "PV003",
                f"state s{s.idx} ({s.kind} on "
                f"{','.join(s.streams)}) is unreachable from the start "
                f"state", query=a.query))

    # accept liveness: PV002 when no start can reach accept — either
    # structurally, or because pruning proved a condition statically
    # false / a dead-start shape (the kernel suppresses arming there)
    if a.statically_dead or accept not in seen:
        why = "a condition folds to constant false" \
            if a.statically_dead and not a.dead_start else \
            "the SEQUENCE leading kleene min>=2 barrier kills every " \
            "sub-min accumulator" if a.dead_start else \
            "no transition path reaches accept"
        diags.append(Diagnostic(
            "PV002",
            f"accept state is unreachable — the pattern can never "
            f"match ({why}); the device step is skipped for this plan",
            query=a.query))

    # PV004 — liveness pruning report
    if a.pruned_states or a.simplified_conditions:
        diags.append(Diagnostic(
            "PV004",
            f"liveness pruning removed {a.pruned_states} state(s) and "
            f"simplified {a.simplified_conditions} condition(s); match "
            f"output is unchanged",
            query=a.query,
            extra={"pruned_states": a.pruned_states,
                   "simplified_conditions": a.simplified_conditions,
                   "notes": list(a.prune_notes)}))

    # PV005 — `within` bound vs summed absent waits on the match path
    if a.within_ms is not None:
        absent_wait = sum(s.waiting_ms for s in a.states
                          if s.kind == "absent")
        if absent_wait and absent_wait >= a.within_ms:
            diags.append(Diagnostic(
                "PV005",
                f"summed `not ... for t` waits ({absent_wait} ms) reach "
                f"the `within` bound ({a.within_ms} ms): partials expire "
                f"before the absence chain can confirm", query=a.query))
    return diags


# ====================================================== jaxpr sanitation

def _walk_jaxpr(jaxpr, prims: set, dtypes: set) -> None:
    """Collect primitive names + aval dtypes, descending into scan/cond/
    pjit sub-jaxprs."""
    for v in list(jaxpr.invars) + list(jaxpr.outvars):
        dt = getattr(getattr(v, "aval", None), "dtype", None)
        if dt is not None:
            dtypes.add(str(dt))
    for eqn in jaxpr.eqns:
        prims.add(eqn.primitive.name)
        for v in list(eqn.invars) + list(eqn.outvars):
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None:
                dtypes.add(str(dt))
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", None)
            if sub is not None:
                _walk_jaxpr(sub, prims, dtypes)
            elif hasattr(p, "eqns"):
                _walk_jaxpr(p, prims, dtypes)
            elif isinstance(p, (list, tuple)):
                for x in p:
                    sub = getattr(x, "jaxpr", None)
                    if sub is not None:
                        _walk_jaxpr(sub, prims, dtypes)


def sanitize_step(kernel: str, fn, *args, elementwise: bool = False,
                  query: Optional[str] = None) -> List[Diagnostic]:
    """Trace ``fn(*args)`` to a jaxpr and scan it (PV010-PV013).

    ``elementwise=True`` declares the kernel a pure column map (the
    device filter program): any gather/scatter is then PV013."""
    import jax

    diags: List[Diagnostic] = []
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:  # noqa: BLE001 — any trace failure is the finding
        diags.append(Diagnostic(
            "PV012",
            f"kernel '{kernel}' could not be traced to a static jaxpr "
            f"({type(e).__name__}: {str(e).splitlines()[0][:160]})",
            query=query))
        return diags
    prims: set = set()
    dtypes: set = set()
    _walk_jaxpr(closed.jaxpr, prims, dtypes)

    hits = sorted(prims & _CALLBACK_PRIMS)
    if hits:
        diags.append(Diagnostic(
            "PV010",
            f"kernel '{kernel}' jaxpr contains host callback primitive(s) "
            f"{hits} — every step round-trips to Python", query=query))
    f64 = sorted(d for d in dtypes if d in ("float64", "complex128"))
    if f64:
        diags.append(Diagnostic(
            "PV011",
            f"kernel '{kernel}' jaxpr carries {f64} values — TPUs "
            f"emulate f64 in software and the engine lane contract is "
            f"float32", query=query))
    if elementwise:
        ghits = sorted(prims & _GATHER_PRIMS)
        if ghits:
            diags.append(Diagnostic(
                "PV013",
                f"kernel '{kernel}' declares itself elementwise but its "
                f"jaxpr contains {ghits} — lane-crossing addressing that "
                f"breaks TPU vectorization", query=query))
    return diags


def sanitize_runtime(rt) -> List[Diagnostic]:
    """Run the jaxpr sanitizer over every device step of a built
    runtime.  Needs jax (lazy) — callers gate this behind `--plan` /
    explicit opt-in; the automaton + cost passes never need it."""
    diags: List[Diagnostic] = []

    def runtimes():
        for qname, qr in getattr(rt, "query_runtimes", {}).items():
            yield qname, qr
        for pr in getattr(rt, "partition_runtimes", ()):
            if getattr(pr, "device_mode", False):
                for qname, qr in pr.device_query_runtimes.items():
                    yield f"{pr.name}/{qname}", qr

    for qname, qr in runtimes():
        dev = getattr(qr, "device_runtime", None)
        cls = type(dev).__name__
        if cls == "DevicePatternRuntime":
            from ..ops.nfa import build_block_step, make_timer_block
            nfa = dev.nfa
            block = make_timer_block(nfa.n_partitions, 0,
                                     nfa.spec.attr_names)
            diags += sanitize_step(
                "nfa.step", build_block_step(nfa.spec), nfa.carry, block,
                query=qname)
        elif cls == "DeviceFilterRuntime":
            import jax.numpy as jnp
            cols = {a: jnp.zeros((1,), jnp.float32) for a in dev.numeric}
            for nm in dev._slanes.lane_names():
                cols[nm] = jnp.zeros((1,), jnp.float32)
            diags += sanitize_step(
                # the jit itself: tracing it is no launch to book
                "filter.program", dev._program._jitted, cols,
                jnp.zeros((1,), jnp.int32), jnp.zeros((1,), bool),
                elementwise=True, query=qname)
    return diags


# ============================================================= the report

@dataclass
class PlanReport:
    """Everything the plan verifier learned about a built runtime."""
    plan: PlanIR
    cost: CostReport
    diagnostics: List[Diagnostic] = field(default_factory=list)

    @property
    def pruned_states(self) -> int:
        return sum(a.pruned_states for a in self.plan.automata)

    @property
    def ok(self) -> bool:
        return not any(d.severity == Severity.ERROR
                       for d in self.diagnostics)

    def as_dict(self) -> Dict[str, Any]:
        return {"plan": self.plan.as_dict(),
                "cost": self.cost.as_dict(),
                "pruned_states": self.pruned_states,
                "diagnostics": [d.as_dict() for d in self.diagnostics]}


def verify_plan(plan: PlanIR, rt=None,
                hbm_budget_mb: Optional[float] = None,
                jaxpr: bool = False) -> PlanReport:
    """Run the automaton + cost passes over a Plan-IR; with ``rt`` and
    ``jaxpr=True`` additionally sanitize the jitted steps."""
    diags: List[Diagnostic] = []
    for a in plan.automata:
        diags += verify_automaton(a)
    cost = plan_cost(plan)
    diags += cost_diagnostics(cost, hbm_budget_mb=hbm_budget_mb,
                              query=plan.app_name)
    if jaxpr and rt is not None:
        diags += sanitize_runtime(rt)
    return PlanReport(plan=plan, cost=cost, diagnostics=diags)


def attach_plan_analysis(rt, hbm_budget_mb: Optional[float] = None,
                         jaxpr: bool = False) -> PlanReport:
    """Extract + verify a built runtime's plan and merge the findings
    into ``rt.analysis`` (created if the runtime has none): plan
    diagnostics ride the same list as the source-level ones, sorted by
    the same (severity, line, code) key, and the full report is
    available as ``rt.analysis.plan`` (and via GET /stats)."""
    from .analyzer import AnalysisResult
    report = verify_plan(extract_plan(rt), rt=rt,
                         hbm_budget_mb=hbm_budget_mb, jaxpr=jaxpr)
    analysis = getattr(rt, "analysis", None)
    if analysis is None:
        analysis = AnalysisResult(app_name=getattr(rt, "name", None))
        rt.analysis = analysis
    prev = getattr(analysis, "plan", None)
    if prev is not None:     # idempotent re-attach (e.g. CLI --plan with
        #                      jaxpr on after the manager's default pass)
        stale = set(map(id, prev.diagnostics))
        analysis.diagnostics = [d for d in analysis.diagnostics
                                if id(d) not in stale]
    order = {Severity.ERROR: 0, Severity.WARNING: 1, Severity.INFO: 2}
    analysis.diagnostics = sorted(
        analysis.diagnostics + report.diagnostics,
        key=lambda d: (order[d.severity],
                       d.line if d.line >= 0 else 1 << 30, d.code))
    analysis.plan = report
    return report
