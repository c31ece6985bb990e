"""Compile-time semantic analyzer (siddhi_tpu/analysis): one positive +
one clean fixture per diagnostic code, strict-mode promotion, source
spans, CLI, /stats embedding, and an end-to-end validation of the
SP001 retrace-hazard prediction against the shape registry's compile
counters."""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.analysis import CATALOG, Severity, analyze  # noqa: E402
from siddhi_tpu.utils.errors import SiddhiAppValidationException  # noqa: E402

S = "define stream S (sym string, price float, vol long);\n"


def codes(app, **kw):
    return analyze(app, **kw).codes()


def diags(app, code, **kw):
    return [d for d in analyze(app, **kw).diagnostics if d.code == code]


# ------------------------------------------------------------- name errors

def test_sa000_parse_error_carries_position():
    d, = diags("define stream S (a int;", "SA000")
    assert d.severity == Severity.ERROR
    assert d.line == 1


def test_sa001_unknown_source():
    assert "SA001" in codes(S + "from Missing select * insert into Out;")
    assert "SA001" not in codes(S + "from S select * insert into Out;")


def test_sa002_unknown_attribute_with_line():
    app = S + "from S[prce > 10]\nselect sym insert into Out;"
    d, = diags(app, "SA002")
    assert d.line == 2 and d.col == 8
    assert "prce" in d.message
    assert not diags(S + "from S[price > 10] select sym insert into Out;",
                     "SA002")


def test_sa003_ambiguous_attribute():
    app = (S + "define stream R (sym string, price float);\n"
           "from S#window.length(2) join R#window.length(2) "
           "on S.sym == R.sym select price insert into Out;")
    assert "SA003" in codes(app)
    ok = (S + "define stream R (sym string, price float);\n"
          "from S#window.length(2) join R#window.length(2) "
          "on S.sym == R.sym select S.price insert into Out;")
    assert "SA003" not in codes(ok)


def test_sa004_type_mismatch():
    assert "SA004" in codes(
        S + "from S select sym * 2 as x insert into Out;")
    assert "SA004" in codes(
        S + "from S[sym > 5] select sym insert into Out;")
    assert "SA004" in codes(
        S + "from S[price and vol > 1] select sym insert into Out;")
    # string + is concatenation, not a mismatch
    assert "SA004" not in codes(
        S + "from S select sym + '!' as x insert into Out;")


def test_sa005_non_boolean_condition():
    assert "SA005" in codes(
        S + "from S[price + 1] select sym insert into Out;")
    assert "SA005" not in codes(
        S + "from S[price > 1] select sym insert into Out;")


def test_sa006_lossy_promotion():
    d, = diags(S + "from S[vol > price] select sym insert into Out;",
               "SA006")
    assert "2^24" in d.message
    # pure integer comparison is exact
    assert not diags(S + "from S[vol > 100] select sym insert into Out;",
                     "SA006")


def test_sa007_unknown_function():
    assert "SA007" in codes(
        S + "from S select frob:nicate(price) as x insert into Out;")
    assert "SA007" not in codes(
        S + "from S select math:sqrt(price) as x insert into Out;")
    # script functions are known
    app = ("define function twice[python] return double { data[0] * 2 };\n"
           + S + "from S select twice(price) as x insert into Out;")
    assert "SA007" not in codes(app)


def test_sa008_insert_schema_mismatch():
    assert "SA008" in codes(
        S + "define stream Out (a int);\n"
        "from S select sym, price insert into Out;")       # arity
    assert "SA008" in codes(
        S + "define stream Out (a int);\n"
        "from S select sym as a insert into Out;")         # type
    assert "SA008" not in codes(
        S + "define stream Out (a float);\n"
        "from S select price as a insert into Out;")


# --------------------------------------------------------- unbounded state

def test_sa020_within_less_every_pattern():
    bad = (S + "from every e1=S[price > 1] -> e2=S[price > e1.price]\n"
           "select e1.price as p insert into Out;")
    assert "SA020" in codes(bad)
    good = (S + "from every e1=S[price > 1] -> e2=S[price > e1.price] "
            "within 5 sec select e1.price as p insert into Out;")
    assert "SA020" not in codes(good)


def test_sa021_pkless_table_append():
    assert "SA021" in codes(
        S + "define table T (sym string);\n"
        "from S select sym insert into T;")
    assert "SA021" not in codes(
        S + "@PrimaryKey('sym') define table T (sym string);\n"
        "from S select sym insert into T;")


def test_sa022_windowless_grouped_aggregation():
    assert "SA022" in codes(
        S + "from S select sym, sum(price) as t group by sym "
        "insert into Out;")
    assert "SA022" not in codes(
        S + "from S#window.length(8) select sym, sum(price) as t "
        "group by sym insert into Out;")


# -------------------------------------------------------- partition safety

def test_sa030_partition_shared_table_write():
    app = (S + "define table T (sym string);\n"
           "partition with (sym of S) begin\n"
           "from S select sym insert into T;\nend;")
    assert "SA030" in codes(app)
    outside = (S + "define table T (sym string);\n"
               "from S select sym insert into T;")
    assert "SA030" not in codes(outside)


def test_sa031_partition_shared_window_write():
    app = (S + "define window W (sym string) length(5);\n"
           "partition with (sym of S) begin\n"
           "from S select sym insert into W;\nend;")
    assert "SA031" in codes(app)


# --------------------------------------------------------------- dead code

def test_sa040_unused_stream():
    assert "SA040" in codes(
        S + "define stream Orphan (x int);\n"
        "from S select sym insert into Out;")
    # @source-annotated streams are externally fed, not dead
    assert "SA040" not in codes(
        S + "@source(type='inMemory', topic='t') "
        "define stream Orphan (x int);\n"
        "from S select sym insert into Out;")


def test_sa041_unused_attribute():
    d, = diags(S + "from S select sym, price insert into Out;", "SA041")
    assert "vol" in d.message
    assert not diags(S + "from S select * insert into Out;", "SA041")


# ------------------------------------------------------------ perf hazards

def test_sp001_retrace_only_on_device_modes():
    bad = (S + "from every e1=S[price > 1] -> e2=S[price > e1.price]\n"
           "select e1.price as p insert into Out;")
    assert "SP001" in codes(bad)
    assert "SP001" not in codes(bad, engine="host")


def test_sp002_partition_lane_growth_info():
    app = (S + "partition with (sym of S) begin\n"
           "from S select sym, price insert into Out;\nend;")
    d, = diags(app, "SP002")
    assert d.severity == Severity.INFO
    assert not diags(app, "SP002", engine="host")


def test_sp003_dynamic_window_param():
    assert "SP003" in codes(
        S + "from S#window.length(vol) select sym insert into Out;")
    assert "SP003" not in codes(
        S + "from S#window.length(5) select sym insert into Out;")
    # externalTime's FIRST param is legitimately an attribute
    assert "SP003" not in codes(
        S + "from S#window.externalTime(vol, 1 sec) "
        "select sym insert into Out;")


def test_sp010_host_fallback_prediction():
    # group-by on a pattern query is host-only
    app = (S + "from every e1=S[price > 1] -> e2=S[price > 2] "
           "within 5 sec select e1.sym as k, count() as c group by k "
           "insert into Out;")
    assert "SP010" in codes(app)
    clean = (S + "from every e1=S[price > 1] -> e2=S[price > 2] "
             "within 5 sec select e1.price as p insert into Out;")
    assert "SP010" not in codes(clean)


def test_sp011_int_precision_above_2p24():
    app = (S + "from every e1=S[vol > 20000000] -> e2=S[vol > e1.vol] "
           "within 5 sec select e1.vol as v insert into Out;")
    assert "SP011" in codes(app)
    small = (S + "from every e1=S[vol > 200] -> e2=S[vol > e1.vol] "
             "within 5 sec select e1.vol as v insert into Out;")
    assert "SP011" not in codes(small)


# ------------------------------------------------- acceptance fixture

ACCEPTANCE = """define stream S (sym string, price float, vol long);
define table T (sym string, price float);
@info(name='q1')
from S[prce > 10]
select sym, price
insert into Alerts;
@info(name='q2')
from every e1=S[price > 100] -> e2=S[price > e1.price]
select e1.price as p1, e2.price as p2
insert into Out;
partition with (sym of S)
begin
  @info(name='q3')
  from S select sym, price insert into T;
end;
"""


def test_acceptance_fixture_three_codes_with_lines():
    r = analyze(ACCEPTANCE)
    by_code = {d.code: d for d in r.diagnostics}
    # >= 3 distinct codes across the three seeded problems
    assert {"SA002", "SA020", "SA030"} <= set(by_code)
    assert len(r.codes()) >= 3
    assert by_code["SA002"].line == 4          # misspelled attribute
    assert by_code["SA020"].line == 8          # within-less every
    assert by_code["SA030"].line == 14         # partition table write
    assert not r.ok


def test_acceptance_fixture_strict_fails_fast():
    m = SiddhiManager()
    with pytest.raises(SiddhiAppValidationException):
        m.create_siddhi_app_runtime(ACCEPTANCE, strict=True)
    assert not m.runtimes        # nothing was built or registered


def test_strict_promotes_warning_only_app():
    app = (S + "from every e1=S[price > 1] -> e2=S[price > e1.price]\n"
           "select e1.price as p insert into Out;")
    m = SiddhiManager()
    with pytest.raises(SiddhiAppValidationException):
        m.create_siddhi_app_runtime(app, strict=True)
    # non-strict builds fine and carries the result
    rt = m.create_siddhi_app_runtime(app)
    try:
        assert rt.analysis is not None
        assert "SA020" in rt.analysis.codes()
    finally:
        rt.shutdown()


def test_strict_accepts_clean_app():
    app = (S + "from S[price > 10] select sym, price, vol "
           "insert into Out;")
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app, strict=True)
    try:
        assert rt.analysis.ok and not rt.analysis.warnings
    finally:
        rt.shutdown()


def test_fluent_api_app_analyzes_without_positions():
    from siddhi_tpu.query_api import (Expression, Query, Selector,
                                      SiddhiApp, SingleInputStream,
                                      StreamDefinition)
    app = SiddhiApp()
    app.define_stream(
        StreamDefinition("S").attribute("a", "int"))
    q = (Query.query()
         .from_(SingleInputStream("S"))
         .select(Selector().select("b", Expression.variable("missing")))
         .insert_into("Out"))
    app.add_query(q)
    r = analyze(app)
    assert "SA002" in r.codes()
    d, = [d for d in r.diagnostics if d.code == "SA002"]
    assert d.line == -1          # no text, no spans — must not crash


# ------------------------------------------------------------ integration

def test_stats_surface_embeds_analysis():
    from siddhi_tpu.service.rest import SiddhiService
    svc = SiddhiService(port=0)
    app = ("@app:name('ana') " + S +
           "from every e1=S[price > 1] -> e2=S[price > e1.price]\n"
           "select e1.price as p insert into Out;")
    rt = svc.manager.create_siddhi_app_runtime(app)
    try:
        doc = svc._stats_json()
        ana = doc["apps"]["ana"]["analysis"]
        assert any(d["code"] == "SA020" for d in ana)
        assert all("severity" in d and "line" in d for d in ana)
    finally:
        rt.shutdown()


def test_cli_pretty_json_and_exit_codes(tmp_path, capsys):
    from siddhi_tpu.analyze import main
    bad = tmp_path / "bad.siddhi"
    bad.write_text(ACCEPTANCE)
    assert main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "SA002" in out and "bad.siddhi:4" in out

    assert main([str(bad), "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert not doc["ok"]
    assert any(d["code"] == "SA020" for d in doc["diagnostics"])

    warn_only = tmp_path / "warn.siddhi"
    warn_only.write_text(
        S + "from every e1=S[price > 1] -> e2=S[price > e1.price]\n"
        "select e1.price as p insert into Out;")
    assert main([str(warn_only)]) == 0
    capsys.readouterr()
    assert main([str(warn_only), "--strict"]) == 1
    capsys.readouterr()

    clean = tmp_path / "ok.siddhi"
    clean.write_text(S + "from S[price > 1] select sym, price, vol "
                     "insert into Out;")
    assert main([str(clean), "--strict"]) == 0


def test_catalog_docs_cover_every_code():
    text = open(os.path.join(os.path.dirname(__file__), "..", "docs",
                             "analysis.md")).read()
    for code in CATALOG:
        assert code in text, f"docs/analysis.md missing {code}"


def test_catalog_docs_are_generated_verbatim():
    """docs/analysis.md embeds catalog_markdown() output verbatim, so
    the document can never drift from diagnostics.CATALOG — adding a
    code without regenerating (`python -m siddhi_tpu.analyze
    --catalog-md`) fails here."""
    from siddhi_tpu.analysis import catalog_markdown
    text = open(os.path.join(os.path.dirname(__file__), "..", "docs",
                             "analysis.md")).read()
    assert catalog_markdown() in text, (
        "docs/analysis.md catalog section is stale — regenerate with "
        "python -m siddhi_tpu.analyze --catalog-md")


# ------------------------------------------- SP001 vs compile counts (e2e)

def test_sp001_prediction_matches_registry_retraces():
    """The retrace-hazard pass predicts that a within-less `every`
    pattern grows its slot ring and re-JITs.  Validate end-to-end: feed
    enough arming events to overflow the default 8-slot ring and assert
    the shape registry's compile counters actually rose — the analyzer's
    SP001 is a *prediction* of exactly this counter movement."""
    from siddhi_tpu.plan.shapes import shape_registry

    app = (S + "@info(name='q') "
           "from every e1=S[vol == 0] -> e2=S[vol == 1 and "
           "price > e1.price] select e1.price as p1 insert into Out;")
    assert "SP001" in codes(app)

    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app)
    try:
        dev = getattr(rt.query_runtimes["q"], "device_runtime", None)
        if dev is None or dev.backend != "device":
            pytest.skip("device pattern path unavailable on this backend")
        rt.add_callback("Out", StreamCallback(lambda evs: None))
        rt.start()
        h = rt.get_input_handler("S")

        def arm_batch(t0):
            n = 8
            h.send_batch({"sym": np.asarray(["k"] * n, object),
                          "price": np.arange(n, dtype=np.float32),
                          "vol": np.zeros(n, np.int64)},
                         timestamps=t0 + np.arange(n, dtype=np.int64))

        arm_batch(1_000)             # warmup: compiles, fills 8 slots
        rt.flush()
        before = shape_registry().totals()["compiles"]
        arm_batch(2_000)             # same shape → only growth recompiles
        rt.flush()
        after = shape_registry().totals()["compiles"]
        assert after > before, (
            "slot-ring growth should have re-JIT'd the NFA step "
            f"(compile_count {before} -> {after})")
    finally:
        rt.shutdown()
