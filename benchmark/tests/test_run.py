"""run.py end to end at a tiny size on the CPU: every stage, every kind of
cell, cells and a metric added as files, and the timed path broken
underneath (`correct` has to come out false).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny  # noqa: E402


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root, cells = tiny.make_root(tmp_path_factory.mktemp("bench") / "root")
    return tiny.load_run(root), root, cells


def _result_ok(out, cell_metrics):
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == set(cell_metrics), out["metrics"]
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
    json.dumps(out)


@pytest.mark.parametrize("cell,metrics", [
    ("tiny_pattern_10k.saturate", {"events_per_s", "setup_s"}),
    ("tiny_agg_keyed_1k.saturate", {"events_per_s", "setup_s"}),
    ("tiny_pattern_10k.paced",
     {"match_latency_p50_ms", "match_latency_p95_ms", "setup_s"}),
    ("tiny_agg_keyed_1k.paced",
     {"match_latency_p50_ms", "match_latency_p95_ms", "setup_s"}),
])
def test_cells_added_as_files_run_and_are_correct(copy, cell, metrics):
    run, _root, cells = copy
    assert cell in cells
    out = run.execute(tiny.opts(cell), require_tpu=False)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    _result_ok(out, metrics)


def test_traced_run_reports_per_layer_metrics_and_the_added_one(copy):
    """On the CPU there is no device plane: the trace-fed metrics are left
    out, never reported as 0; the ledger- and registry-fed ones, and the
    metric dropped in as a new file, are there."""
    run, _root, _cells = copy
    out = run.execute(tiny.opts("tiny_agg_keyed_1k.saturate", trace=1),
                      require_tpu=False)
    assert out["correct"], out["compared"]
    got = set(out["metrics"])
    assert {"admit_share.sat", "dispatch_share.sat", "egress_share.sat",
            "compiles_in_window.sat", "publish_share.sat"} <= got
    assert not {"step_roofline.sat", "kernel_ms_per_mev.sat"} & got
    assert out["metrics"]["publish_share.sat"]["value"] > 0
    assert "busy_s" in out["device"] and "window_s" in out["device"]


def _broken(run, fault):
    """A system_factory that breaks the timed path underneath."""
    sys.path.insert(0, os.path.dirname(run.__file__))
    from system import Served

    class HalfBatch(Served):
        def send(self, cols, ts):
            n = len(ts) // 2
            super().send({k: v[:n] for k, v in cols.items()}, ts[:n])

    class AlteredAnswer(Served):
        def _receive(self, q, chunk):
            super()._receive(q, chunk)
            col = sorted(k for k, v in self.chunks[-1][3].items()
                         if v.dtype.kind == "f")[-1]
            self.chunks[-1][3][col][-1] *= 1.01

    class StateForgotten(Served):
        """The app is rebuilt at the window's opening flush: whatever
        state the warm-up built is gone, as with a step that hands back
        its state unchanged."""
        flushes = 0

        def flush(self):
            super().flush()
            self.flushes += 1
            if self.flushes == self.forget_at:
                chunks = self.chunks
                self.rt.shutdown()
                Served.__init__(self, self.config)
                self.chunks = chunks
                self.flushes = self.forget_at

    # warm_up flushes after every send of its ladder (two sizes in the
    # tiny cells) and once after its stretch; measure() flushes once more
    # before the window opens
    StateForgotten.forget_at = 2 * run.LADDER_REPEATS + 1 + 1
    return {"half_batch": HalfBatch, "altered_answer": AlteredAnswer,
            "state_forgotten": StateForgotten}[fault]


@pytest.mark.parametrize("cell", ["tiny_pattern_10k.saturate",
                                  "tiny_agg_keyed_1k.paced"])
@pytest.mark.parametrize("fault", ["half_batch", "altered_answer",
                                   "state_forgotten"])
def test_a_broken_timed_path_is_not_correct(copy, cell, fault):
    run, _root, _cells = copy
    out = run.execute(tiny.opts(cell, seed=5), require_tpu=False,
                      system_factory=_broken(run, fault))
    assert not out["correct"], out["compared"]
    assert out["failed"] > 0 or any(
        k.startswith("relerr") for k in out["compared"])


def test_no_chip_means_no_result(copy):
    """As the driver starts it, with JAX held to the CPU: exit code 2 and
    no result line."""
    _run, root, cells = copy
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=tiny.REPO)
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cells[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 2, (p.returncode, p.stderr[-500:])
    assert not p.stdout.strip().endswith("}")


def _tool(root, module, *args):
    """A tool's main() in a process of its own, without the look for a
    chip (as execute(require_tpu=False) does for a run)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=tiny.REPO)
    code = (f"import sys; sys.path.insert(0, {os.path.join(root, 'benchmark')!r}); "
            f"import {module}; {module}.main({list(args)!r}, require_tpu=False)")
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-800:]
    return p.stdout.splitlines()


def test_sweep_and_control_rehearse_on_the_cpu(copy):
    """sweep.py: one row per rate, lowest first, each with its repeated
    windows and a verdict; control.py: the program correct, the bfloat16
    control not, per seed."""
    _run, root, _cells = copy
    rows = [json.loads(ln[len("[sweep] "):])
            for ln in _tool(root, "sweep", "--workload",
                            "tiny_agg_keyed_1k.paced", "--rates",
                            "20000,10000", "--seconds", "0.5", "--repeats",
                            "2")
            if ln.startswith("[sweep] {")]
    assert [r["rate"] for r in rows] == [10000.0, 20000.0]
    assert all(len(r["windows"]) == 2 and isinstance(r["sustained"], bool)
               for r in rows)
    assert all(w["p95_ms"] >= w["p50_ms"] > 0 and w["growth"] > 0
               for r in rows for w in r["windows"])
    for cell in ("tiny_agg_keyed_1k.saturate", "tiny_pattern_10k.paced"):
        out = [json.loads(ln[len("[control] "):])
               for ln in _tool(root, "control", "--workload", cell,
                               "--seeds", "1,2", "--seconds", "0.5")
               if ln.startswith("[control] ")]
        assert [o["seed"] for o in out] == [1, 2]
        assert all(o["program_correct"] and not o["control_correct"]
                   for o in out), out


def test_run_py_names_no_cell_config_or_metric():
    with open(os.path.join(tiny.BENCH, "run.py")) as f:
        text = f.read()
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [c["name"] for c in bench["configs"]] + \
        [w["name"] for w in bench["workloads"]] + \
        [m["name"] for m in bench["per_layer"] + bench["end_to_end"]] + \
        [w["traffic"] for w in bench["workloads"]]
    assert not [n for n in names if n in text]
