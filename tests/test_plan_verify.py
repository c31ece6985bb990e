"""Plan-level verifier, liveness pruning and static cost model
(PR 3 tentpole): every PV/PC code fires at least once (asserted against
the catalog), pruning is proven match-output-identical on randomized
feeds, and the cost model's HBM predictions are byte-exact against the
real carries."""
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.analysis import CATALOG  # noqa: E402
from siddhi_tpu.analysis.cost_model import (DEFAULT_FLOPS_WARN,  # noqa: E402
                                            bank_state_bytes,
                                            cost_diagnostics,
                                            nfa_flops_per_event, plan_cost,
                                            nfa_state_bytes)
from siddhi_tpu.analysis.plan_ir import (AutomatonIR, StateIR,  # noqa: E402
                                         automaton_ir_from_nfa,
                                         extract_plan)
from siddhi_tpu.analysis.plan_verify import (sanitize_step,  # noqa: E402
                                             verify_automaton, verify_plan)
from siddhi_tpu.plan.nfa_compiler import CompiledPatternNFA  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STREAM = "define stream S (price float, kind int);\n"


def _nfa(app, **kw):
    kw.setdefault("n_partitions", 2)
    kw.setdefault("mesh", None)
    return CompiledPatternNFA(STREAM + app, **kw)


def _feed(n=240, seed=0, parts=2):
    rng = np.random.default_rng(seed)
    pids = rng.integers(0, parts, n).astype(np.int64)
    cols = {"price": rng.uniform(0, 100, n).astype(np.float32),
            "kind": rng.integers(0, 3, n).astype(np.float32)}
    ts = 1_000_000 + np.cumsum(rng.integers(0, 800, n)).astype(np.int64)
    return pids, cols, ts


def _matches(nfa, feed):
    pids, cols, ts = feed
    return nfa.process_events(pids, cols, ts)


def _ir(**kw):
    """Minimal hand-built AutomatonIR for table-shape tests."""
    states = kw.pop("states")
    defaults = dict(query="q", transitions=[], start_states=(0,),
                    within_ms=None, n_partitions=1, n_slots=8,
                    n_rows=len(states), n_caps=1, n_attrs=2)
    defaults.update(kw)
    return AutomatonIR(states=states, **defaults)


def _codes(diags):
    return {d.code for d in diags}


# ================================================== automaton verification

def test_pv001_dangling_transition():
    a = _ir(states=[StateIR(0, "simple", ("S",), ("e1",))],
            transitions=[(0, "advance", 5)])
    codes = _codes(verify_automaton(a))
    assert codes == {"PV001"} and "PV001" in CATALOG


def test_pv002_accept_unreachable_graph():
    a = _ir(states=[StateIR(0, "simple", ("S",), ("e1",)),
                    StateIR(1, "simple", ("S",), ("e2",))],
            transitions=[(0, "stay", 0), (1, "accept", 2)])
    codes = _codes(verify_automaton(a))
    assert "PV002" in codes          # accept unreachable from start
    assert "PV003" in codes          # s1 unreachable


def test_pv005_within_starved_absent():
    a = _ir(states=[StateIR(0, "simple", ("S",), ("e1",)),
                    StateIR(1, "absent", ("S",), ("e2",),
                            waiting_ms=10_000)],
            transitions=[(0, "advance", 1), (1, "accept", 2)],
            within_ms=5_000)
    assert "PV005" in _codes(verify_automaton(a))


def test_pv005_from_real_app():
    # the absence needs 10s to confirm but every partial dies at 5s
    nfa = _nfa("from every e1=S[kind == 0] -> e2=S[kind == 1] -> "
               "not S[kind == 2] for 10 sec within 5 sec "
               "select e1.price as p1 insert into Out;")
    ir = automaton_ir_from_nfa(nfa, "q")
    assert "PV005" in _codes(verify_automaton(ir))


def test_clean_chain_no_pv_findings():
    nfa = _nfa("from every e1=S[kind == 0] -> e2=S[kind == 1] "
               "within 10 sec select e1.price as p1 insert into Out;")
    diags = verify_automaton(automaton_ir_from_nfa(nfa, "q"))
    assert not [d for d in diags if d.code.startswith("PV")]


def test_healthy_mid_chain_min0_kleene_not_flagged():
    # a LIVE min-0 kleene is epsilon-skipped but keeps appending — it
    # must be reachable in the derived table (no spurious PV003)
    nfa = _nfa("from e1=S[kind == 0] -> e2=S[kind == 2]<0:3> -> "
               "e3=S[kind == 1] "
               "select e1.price as p1, e3.price as p3 insert into Out;")
    assert nfa.prune_report["pruned_states"] == 0
    diags = verify_automaton(automaton_ir_from_nfa(nfa, "q"))
    assert not [d for d in diags if d.code.startswith("PV")], \
        [d.render() for d in diags]


# ================================================== liveness pruning

DEAD_APP = ("from e1=S[kind == 0 and 1 > 2] -> e2=S[kind == 1] "
            "select e1.price as p1 insert into Out;")
PRUNABLE_KLEENE = ("from e1=S[kind == 0] -> "
                   "e2=S[kind == 2 and 1 == 2]<0:3> -> e3=S[kind == 1] "
                   "select e1.price as p1, e3.price as p3 insert into Out;")
PRUNABLE_OR = ("from e1=S[kind == 0] -> "
               "e2=S[kind == 1] or e3=S[kind == 2 and 1 > 3] "
               "select e1.price as p1 insert into Out;")
SIMPLIFIABLE = ("from every e1=S[kind == 0 and 2 > 1] -> "
                "e2=S[kind == 1 and price > e1.price] within 20 sec "
                "select e1.price as p1, e2.price as p2 insert into Out;")


def test_dead_pattern_detected_and_step_skipped():
    nfa = _nfa(DEAD_APP)
    assert nfa.statically_dead and nfa.prune_report["dead"]
    assert _matches(nfa, _feed()) == []
    # PV002 rides the runtime's plan analysis
    ir = automaton_ir_from_nfa(nfa, "q")
    assert "PV002" in _codes(verify_automaton(ir))


def test_seq_dead_start_short_circuits():
    nfa = _nfa("from e1=S[kind == 0]<2:4>, e2=S[kind == 1] "
               "select e2.price as p2 insert into Out;")
    assert nfa.spec.dead_start and nfa.statically_dead
    assert _matches(nfa, _feed()) == []


@pytest.mark.parametrize("app,pruned", [
    (DEAD_APP, 0), (PRUNABLE_KLEENE, 1), (PRUNABLE_OR, 1),
    (SIMPLIFIABLE, 0)])
def test_pruned_vs_unpruned_identical_matches(app, pruned):
    """The equivalence proof: pruned and unpruned compiles of the same
    pattern produce identical match streams on randomized event feeds."""
    a = _nfa(app)
    b = _nfa(app, prune=False)
    assert a.prune_report["pruned_states"] == pruned
    assert b.prune_report["pruned_states"] == 0
    for seed in (0, 1, 2):
        feed = _feed(seed=seed)
        assert _matches(a, feed) == _matches(b, feed), \
            f"seed {seed}: pruned output diverged"


def test_prune_keeps_referenced_dead_capture():
    # the dead min-0 kleene's capture is selected -> must NOT be deleted
    # (its output column is always-null and must stay addressable)
    app = ("from e1=S[kind == 0] -> e2=S[kind == 2 and 1 == 2]<0:3> -> "
           "e3=S[kind == 1] "
           "select e1.price as p1, e2.price as p2, e3.price as p3 "
           "insert into Out;")
    a = _nfa(app)
    assert a.prune_report["pruned_states"] == 0
    b = _nfa(app, prune=False)
    for seed in (0, 3):
        feed = _feed(seed=seed)
        assert _matches(a, feed) == _matches(b, feed)


def test_prune_false_is_the_unpruned_reference():
    nfa = _nfa(PRUNABLE_KLEENE, prune=False)
    assert not nfa.prune_enabled
    assert nfa.prune_report["pruned_states"] == 0


def test_pv004_and_pruned_counts_ride_rt_analysis():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        STREAM + "@info(name='q') " + PRUNABLE_KLEENE)
    try:
        assert "PV004" in rt.analysis.codes()
        assert rt.analysis.plan is not None
        assert rt.analysis.plan.pruned_states == 1
    finally:
        rt.shutdown()


def test_dead_pattern_through_engine_delivers_nothing():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        STREAM + "@info(name='q') " + DEAD_APP)
    try:
        assert "PV002" in rt.analysis.codes()
        got = []
        rt.add_callback("Out", StreamCallback(lambda evs: got.extend(evs)))
        rt.start()
        pids, cols, ts = _feed(n=64)
        rt.get_input_handler("S").send_batch(
            {"price": cols["price"], "kind": cols["kind"].astype(np.int64)},
            timestamps=ts)
        rt.flush()
        assert got == []
    finally:
        rt.shutdown()


# ================================================== jaxpr kernel sanitizer

def test_pv010_host_callback():
    import jax
    import jax.numpy as jnp

    def fn(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
    diags = sanitize_step("k", fn, jnp.zeros((4,), jnp.float32))
    assert _codes(diags) == {"PV010"} and "PV010" in CATALOG


def test_pv011_float64_upcast():
    import jax
    import jax.numpy as jnp
    with jax.enable_x64(True):
        diags = sanitize_step(
            "k", lambda x: x * 2.0, jnp.zeros((4,), jnp.float64))
    assert "PV011" in _codes(diags)


def test_pv012_dynamic_shape():
    import jax.numpy as jnp

    def fn(x):
        return x[x > 0]          # boolean mask: data-dependent shape
    diags = sanitize_step("k", fn, jnp.arange(4, dtype=jnp.float32))
    assert _codes(diags) == {"PV012"}


def test_pv013_gather_in_elementwise_kernel():
    import jax.numpy as jnp

    def fn(x, idx):
        return x[idx]
    args = (jnp.arange(8, dtype=jnp.float32),
            jnp.zeros((4,), jnp.int32))
    assert "PV013" in _codes(sanitize_step("k", fn, *args,
                                           elementwise=True))
    # the same jaxpr is fine for a kernel that declares gather
    assert "PV013" not in _codes(sanitize_step("k", fn, *args))


def test_nfa_step_and_filter_program_sanitize_clean():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        STREAM +
        "@info(name='p') from every e1=S[kind == 0] -> e2=S[kind == 1] "
        "within 10 sec select e1.price as p1 insert into Out;\n"
        "@info(name='f') from S[price > 50] select price insert into F;")
    try:
        from siddhi_tpu.analysis.plan_verify import sanitize_runtime
        diags = sanitize_runtime(rt)
        assert not diags, [d.render() for d in diags]
    finally:
        rt.shutdown()


# ================================================== static cost model

def test_pc001_summary_on_device_plan():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        STREAM + "@info(name='q') from every e1=S[kind == 0] -> "
        "e2=S[kind == 1] within 10 sec "
        "select e1.price as p1 insert into Out;")
    try:
        assert "PC001" in rt.analysis.codes()
        cost = rt.analysis.plan.cost
        assert cost.total_hbm_bytes > 0
        assert cost.total_flops_per_event > 0
    finally:
        rt.shutdown()


def test_pc002_budget_gate():
    nfa = _nfa("from every e1=S[kind == 0] -> e2=S[kind == 1] "
               "within 10 sec select e1.price as p1 insert into Out;")
    plan = verify_plan(_plan_of(nfa), hbm_budget_mb=1e-6)
    assert "PC002" in _codes(plan.diagnostics)


def _plan_of(nfa):
    from siddhi_tpu.analysis.plan_ir import PlanIR
    return PlanIR(app_name="t", automata=[automaton_ir_from_nfa(nfa, "q")])


def test_pc003_flops_threshold():
    nfa = _nfa("from every e1=S[kind == 0] -> e2=S[kind == 1] "
               "within 10 sec select e1.price as p1 insert into Out;")
    report = plan_cost(_plan_of(nfa))
    assert "PC003" in _codes(cost_diagnostics(report, flops_warn=1))
    assert "PC003" not in _codes(
        cost_diagnostics(report, flops_warn=DEFAULT_FLOPS_WARN))


@pytest.mark.parametrize("app", [
    "from every e1=S[kind == 0] -> e2=S[kind == 1 and price > e1.price] "
    "within 10 sec select e1.price as p1 insert into Out;",
    "from e1=S[kind == 0] -> e2=S[kind == 1]<1:3> -> "
    "e3=S[kind == 0] -> not S[kind == 2] for 5 sec "
    "select e1.price as p1 insert into Out;",
    "from every e1=S[kind == 0], e2=S[kind == 1] "
    "select e1.price as p1 insert into Out;",
])
def test_hbm_prediction_byte_exact(app):
    nfa = _nfa(app, n_partitions=3)
    ir = automaton_ir_from_nfa(nfa, "q")
    predicted = sum(nfa_state_bytes(ir).values())
    actual = sum(int(np.asarray(v).nbytes) for v in nfa.carry.values())
    assert predicted == actual
    assert nfa_flops_per_event(ir) > 0


def test_bank_prediction_matches_live_bytes_gauge():
    import gc

    from siddhi_tpu.plan.nfa_compiler import CompiledPatternBank
    from siddhi_tpu.plan.shapes import shape_registry
    apps = [STREAM + f"from every e1=S[kind == 0 and price > {t}] -> "
            "e2=S[kind == 1] within 10 sec "
            "select e1.price as p1 insert into Out;"
            for t in (10.0, 50.0)]
    gc.collect()    # the gauge sums the live engines of a kind
    held = shape_registry().kernels().get("nfa.bank_step",
                                          {"live_bytes": 0})["live_bytes"]
    bank = CompiledPatternBank(apps, n_partitions=4, n_slots=4,
                               pattern_chunk=2)
    ir = automaton_ir_from_nfa(bank.nfa, "bank")
    predicted = bank_state_bytes(ir, 2, n_partitions=4)
    measured = shape_registry().kernels()["nfa.bank_step"]["live_bytes"] \
        - held
    assert measured > 0
    # acceptance bound is 2x; the formulas are in fact byte-exact
    assert predicted == measured


# ================================================== surfaces

def test_stats_json_embeds_plan_report():
    from siddhi_tpu.service.rest import SiddhiService
    svc = SiddhiService(port=0)
    try:
        rt = svc.manager.create_siddhi_app_runtime(
            "@app:statistics(enable='true') " + STREAM +
            "@info(name='q') from every e1=S[kind == 0] -> "
            "e2=S[kind == 1] within 10 sec "
            "select e1.price as p1 insert into Out;")
        doc = svc._stats_json()
        app_doc = doc["apps"][rt.name]
        assert "plan" in app_doc
        assert app_doc["plan"]["cost"]["total_hbm_bytes"] > 0
        assert app_doc["plan"]["plan"]["automata"][0]["n_states"] == 2
    finally:
        svc.manager.shutdown()


def test_analyze_cli_default_path_imports_no_jax(tmp_path):
    app = tmp_path / "a.siddhi"
    app.write_text(STREAM + "from S[price > 1] select price "
                   "insert into Out;")
    code = ("import sys\n"
            "from siddhi_tpu.analyze import main\n"
            f"rc = main([{str(app)!r}, '--json'])\n"
            "assert 'jax' not in sys.modules, 'jax leaked into the "
            "default analyze path'\n"
            "sys.exit(rc)\n")
    res = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr


def test_analyze_cli_plan_flag(tmp_path):
    app = tmp_path / "a.siddhi"
    app.write_text(
        STREAM + "@info(name='q') from every e1=S[kind == 0] -> "
        "e2=S[kind == 1] within 10 sec "
        "select e1.price as p1 insert into Out;")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "-m", "siddhi_tpu.analyze", str(app),
         "--plan", "--json"],
        cwd=ROOT, capture_output=True, text=True, timeout=300, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    import json
    doc = json.loads(res.stdout)
    assert doc["plan"]["cost"]["total_hbm_bytes"] > 0
    codes = {d["code"] for d in doc["diagnostics"]}
    assert "PC001" in codes


def test_every_new_code_is_in_catalog_and_docs():
    new = {"PV001", "PV002", "PV003", "PV004", "PV005",
           "PV010", "PV011", "PV012", "PV013",
           "PC001", "PC002", "PC003"}
    assert new <= set(CATALOG)
    from siddhi_tpu.analysis import catalog_markdown
    md = catalog_markdown()
    for c in new:
        assert c in md
