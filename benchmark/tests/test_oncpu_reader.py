"""The `oncpu` reader on the CPU: its share off the CPU from the program's
recorded wall and CPU pairs, its metric files' keys declared by the
program, and nothing reported where the program keeps no such pairs
(the parent of the second clock) or recorded no wall time.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
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

from run import load_module  # noqa: E402

MS = 1_000_000
FILES = ("pack_offcpu_share.sat", "launch_offcpu_share.sat")


@pytest.fixture
def recorded(monkeypatch):
    """The global ledger with its spans recorded (the exporter on) on two
    clocks the test advances by hand."""
    import siddhi_tpu.core.ledger as ledger_mod
    from siddhi_tpu.core.tracing import tracer
    clocks = {"wall": 0, "cpu": 0}
    monkeypatch.setattr(ledger_mod, "_pcns", lambda: clocks["wall"])
    monkeypatch.setattr(ledger_mod, "_tns", lambda: clocks["cpu"])
    ledger_mod.ledger().reset()
    tracer().enable()

    def tick(wall_ms, cpu_ms):
        clocks["wall"] += wall_ms * MS
        clocks["cpu"] += cpu_ms * MS

    yield ledger_mod.ledger(), tick
    tracer().disable()
    tracer().clear()
    ledger_mod.ledger().reset()


def _spec(name):
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", FILES)
def test_metric_files_name_declared_keys(name):
    from siddhi_tpu.core.ledger import ONCPU_KEYS
    spec = _spec(name)
    assert spec["reader"] == "oncpu"
    assert spec["args"]["op"] == "offcpu_share"
    assert spec["args"]["keys"]
    assert set(spec["args"]["keys"]) <= set(ONCPU_KEYS)


def test_the_share_off_the_cpu(recorded):
    led, tick = recorded
    reader = load_module("readers", "oncpu")
    with led.span("dispatch", None, 1, "a"):
        with led.span("dispatch", "keys"):
            tick(4, 3)
        with led.span("device", "sync"):
            tick(1, 1)
            with led.span(None, "device.issue/nfa.xstep"):
                tick(4, 1)
        tick(10, 10)            # `dispatch` itself: read by neither
    pack = reader.read({}, op="offcpu_share",
                       keys=_spec("pack_offcpu_share.sat")["args"]["keys"])
    assert pack == pytest.approx(25.0)
    launch = reader.read({}, op="offcpu_share",
                         keys=_spec("launch_offcpu_share.sat")["args"]["keys"])
    assert launch == pytest.approx(100.0 * (1 - 2 / 5))
    with pytest.raises(ValueError):
        reader.read({}, op="share", keys=["device.sync"])


def test_nothing_without_recorded_wall_time(recorded):
    led, _tick = recorded
    reader = load_module("readers", "oncpu")
    assert reader.read({}, op="offcpu_share", keys=["device.pack"]) is None
    assert reader.read({}, op="offcpu_share", keys=["no.such_key"]) is None


def test_nothing_against_a_ledger_without_the_pairs(monkeypatch):
    import siddhi_tpu.core.ledger as ledger_mod

    class ParentLedger:
        """A ledger as it was before the second clock."""

        def stage_ns(self):
            return {"device.sync": 5 * MS}

    monkeypatch.setattr(ledger_mod, "ledger", lambda: ParentLedger())
    reader = load_module("readers", "oncpu")
    assert reader.read({}, op="offcpu_share", keys=["device.sync"]) is None
