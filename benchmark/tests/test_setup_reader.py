"""The `setup` reader, and every per-layer metric of BENCHMARK.json held
to the files and the program it names: its metric file exists, its
reader imports, and every ledger stage, sub-span or wait it reads is one
the program declares (so a renamed span fails here, not in a run)."""
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    PER_LAYER = json.load(_f)["per_layer"]


def _reader(name):
    path = os.path.join(BENCH, "readers", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _metric_file(name):
    """As run.py finds it: metrics/<name>.json, else the file a quantity
    split by what it moves (`x.<suffix>`) shares, metrics/x.json."""
    path = os.path.join(BENCH, "metrics", f"{name}.json")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(BENCH, "metrics",
                            f"{name.rsplit('.', 1)[0]}.json")
    with open(path) as f:
        return json.load(f)


def test_setup_reader_totals_the_registrys_phases():
    import jax.numpy as jnp

    from siddhi_tpu.plan.shapes import shape_registry
    setup = _reader("setup")
    reg = shape_registry()
    reg.reset()
    rj = reg.jit("test.setup_reader", {"n": 1}, lambda x: x * 3 + 1)
    rj(jnp.arange(8))
    # everything the process compiled: the step's entry and what was
    # compiled outside any registered step (the argument, here)
    tot = reg.totals()
    both = setup.read({}, op="total",
                      fields=["trace_seconds", "lower_seconds"])
    assert both == pytest.approx(tot["trace_seconds"] + tot["lower_seconds"])
    assert both >= rj.entry.trace_seconds + rj.entry.lower_seconds > 0
    assert setup.read({}, op="total", fields=["backend_seconds"]) == \
        pytest.approx(tot["backend_seconds"])
    # a program whose registry has no such total reports nothing
    assert setup.read({}, op="total", fields=["no_such_seconds"]) is None
    with pytest.raises(ValueError):
        setup.read({}, op="mean", fields=["trace_seconds"])
    reg.reset()


@pytest.mark.parametrize("metric", [m["name"] for m in PER_LAYER])
def test_every_metric_names_a_file_a_reader_and_declared_spans(metric):
    from siddhi_tpu.core.ledger import SPAN_NAMES, STAGES, WAITS
    spec = _metric_file(metric)
    reader = _reader(spec["reader"])
    assert callable(reader.read)
    if spec["reader"] == "ledger":
        declared = set(STAGES) | set(SPAN_NAMES) | set(WAITS)
        assert spec["args"]["stages"], metric
        assert set(spec["args"]["stages"]) <= declared, metric
    if spec["reader"] == "setup":
        from siddhi_tpu.plan.shapes import shape_registry
        assert set(spec["args"]["fields"]) <= set(shape_registry().totals())
