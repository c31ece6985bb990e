"""chip_smoke.py rehearsed on the CPU, and the failure-hiding paths PR 21
closed stay closed.

  * every chip_smoke stage function runs tiny on the conftest's 8 virtual
    CPU devices (so the pattern stage takes the mesh and the shard-out
    placements; the single-device gang is tests/test_multitenant.py's) and
    the registry ends up holding every kind the script requires there;
  * the command line has no CPU mode: it exits non-zero, names the
    platform it found and prints no result — also in a directory that
    holds nothing else of the repo;
  * a JaxRuntimeError raised while a device runtime builds or warms
    propagates under engine('auto') and engine('device') alike, while a
    shape the device cannot express still routes away with its reason;
  * the compile cache lands where JAX_COMPILATION_CACHE_DIR says, else in
    <repo>/.jax_cache;
  * native_ext builds the packer when it is missing and says so loudly
    when it cannot.
"""
import json
import logging
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from siddhi_tpu import SiddhiManager  # noqa: E402
from siddhi_tpu.plan.shapes import DEFAULT_CACHE_DIR, shape_registry  # noqa: E402
from siddhi_tpu.utils.errors import SiddhiAppCreationError  # noqa: E402

SMOKE = os.path.join(REPO, "chip_smoke.py")


# ------------------------------------------------------------ the rehearsal

@pytest.fixture(scope="module")
def rehearsal():
    """Every stage once at toy sizes; Pallas through the interpreter (the
    one thing the CPU cannot do is let Mosaic compile it)."""
    from jax.experimental import pallas as pl
    shape_registry().reset()
    reps = {}
    reps["pattern"] = chip_smoke.stage_pattern(
        n_keys=50, chunk=512, chunks=3, shard_runs=(0, 2))
    reps["agg"] = chip_smoke.stage_agg(n_keys=20, window=16, chunk=512,
                                       chunks=3)
    reps["bank"] = chip_smoke.stage_bank(
        n_patterns=8, n_partitions=64, pattern_chunk=4, t_blk=8, ring=4,
        blocks=3, check=(0, 7))
    reps["families"] = chip_smoke.stage_families(
        n=512, batch_len=100, join_n=128, join_win=32)
    orig = pl.pallas_call
    pl.pallas_call = lambda *a, **k: orig(*a, **{**k, "interpret": True})
    try:
        reps["pallas"] = chip_smoke.stage_pallas(shapes=((256, 16, 8),))
    finally:
        pl.pallas_call = orig
    reps["table"] = chip_smoke.registry_table()
    return reps


def test_pattern_stage_takes_mesh_then_shards(rehearsal):
    default, sharded = rehearsal["pattern"]
    n_dev = len(jax.devices())
    assert default["rows_equal"] and default["rows_out"] > 0
    assert default["placement"]["mesh"] == n_dev
    assert len(default["placement"]["carry_devices"]) == n_dev
    assert sharded["rows_equal"] and sharded["placement"]["shards"] == 2
    assert len(sharded["placement"]["carry_devices"]) == 2
    assert default["queries"]["q"][0] == sharded["queries"]["q"][0] \
        == "device"


def test_agg_stage_three_routes_on_device(rehearsal):
    by = {r["stage"]: r for r in rehearsal["agg"]}
    assert set(by) == {"agg.wagg_length", "agg.gagg_select",
                       "agg.gagg_select.Top", "agg.wagg_time"}
    assert all(r["rows_equal"] and r["rows_out"] > 0 for r in by.values())
    sel = by["agg.gagg_select"]["queries"]
    assert sel["qwin"] == ("device", None, "device")
    assert sel["qrun"] == ("device", None, "device")


def test_bank_stage_counts_equal_oracle(rehearsal):
    bank = rehearsal["bank"]
    assert bank["counts_equal"] and bank["dropped"] == 0
    assert bank["stacked"] and bank["chunks_per_dispatch"] == 2
    assert [c for c, _s in bank["block_s"]] == [
        "block_until_ready", "block_until_ready", "d2h_read"]


def test_families_and_pallas_stages(rehearsal):
    stages = [r["stage"] for r in rehearsal["families"]]
    assert stages == ["filter", "dwin.lengthBatch", "join.range",
                      "tenants.t0", "tenants.t1"]
    assert all(r["rows_equal"] for r in rehearsal["families"])
    assert rehearsal["pallas"][0]["equal"]
    assert rehearsal["pallas"][0]["mosaic_compiled"]   # use_pallas=True


def test_rehearsal_compiles_every_required_kind(rehearsal):
    table = rehearsal["table"]
    need = chip_smoke.required_kinds(len(jax.devices()))
    assert "nfa.mesh_step" in need and "nfa.xstep" not in need
    assert "nfa.xstep" in chip_smoke.required_kinds(1)
    missing = [k for k in need if table.get(k, {}).get("compiles", 0) < 1]
    assert not missing, (missing, sorted(table))


def test_compare_catches_a_difference():
    t = {"__ts": np.arange(3), "v": np.array([1.0, 2.0, 3.0], np.float32),
         "s": np.array(["a", "b", "c"], object)}
    assert chip_smoke.compare(t, dict(t))["equal"]
    off = dict(t, v=np.array([1.0, 2.0, 3.001], np.float32))
    assert not chip_smoke.compare(t, off)["equal"]
    assert chip_smoke.compare(t, off, rtol=1e-3)["equal"]
    assert not chip_smoke.compare(t, {k: v[:2] for k, v in t.items()})["equal"]
    swapped = {k: v[::-1] for k, v in t.items()}
    assert chip_smoke.compare(t, swapped)["equal"]            # row multiset
    assert not chip_smoke.compare(t, swapped, ())["equal"]    # delivered order


# ------------------------------------------------------- no CPU mode at all

def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=300, env=env, cwd=cwd)


def test_cli_exits_nonzero_on_cpu_and_names_the_platform():
    res = _run_smoke(REPO, SMOKE)
    assert res.returncode not in (0, None), res.stdout + res.stderr
    assert "platform=cpu" in res.stderr
    assert '"ok"' not in res.stdout


def test_cli_exits_nonzero_alone_in_a_directory(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    res = _run_smoke(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert res.returncode != 0
    assert "siddhi_tpu" in res.stderr and '"ok"' not in res.stdout


# ------------------------------------------- no fallback that hides the device

def _oom(*_a, **_k):
    raise jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: injected by tests/test_chip_smoke.py")


KEYED_WAGG = """define stream S (k string, sym string, v float);
partition with (k of S) begin
@info(name='q') from S[{flt}]#window.length(5)
select k, sum(v) as total group by k insert into Out;
end;"""
FILTER = ("define stream S (sym string, price float); @info(name='q') "
          "from S[{flt}] select sym, price insert into Out;")


@pytest.mark.parametrize("engine", ["auto", "device"])
def test_jax_runtime_error_in_wagg_step_builder_propagates(monkeypatch,
                                                           engine):
    # before PR 21 the planner wrapped it as SiddhiAppCreationError and
    # query_runtime rerouted the query to gagg, in every engine mode
    monkeypatch.setattr("siddhi_tpu.plan.wagg_compiler.build_wagg_step",
                        lambda *a, **k: _oom)
    with pytest.raises(jax.errors.JaxRuntimeError, match="injected"):
        SiddhiManager().create_siddhi_app_runtime(
            f"@app:engine('{engine}') " + KEYED_WAGG.format(flt="v > 0.0"))


@pytest.mark.parametrize("engine", ["auto", "device"])
def test_jax_runtime_error_in_filter_warm_trace_propagates(monkeypatch,
                                                           engine):
    # before PR 21 engine('auto') turned it into a host fallback
    from siddhi_tpu.plan.shapes import ShapeRegistry
    real = ShapeRegistry.jit

    def jit(self, kind, dims, fn, **kw):
        return real(self, kind, dims,
                    _oom if kind == "filter.program" else fn, **kw)
    monkeypatch.setattr(ShapeRegistry, "jit", jit)
    with pytest.raises(jax.errors.JaxRuntimeError, match="injected"):
        SiddhiManager().create_siddhi_app_runtime(
            f"@app:engine('{engine}') " + FILTER.format(flt="price > 1.0"))


def test_inexpressible_shapes_still_route_away_with_a_reason():
    # a string function in a filter: plan-time rejection -> host + reason
    rt = SiddhiManager().create_siddhi_app_runtime(
        FILTER.format(flt="str:upper(sym) == 'A'"))
    qr = rt.query_runtimes["q"]
    assert qr.backend == "host" and "string" in qr.backend_reason
    rt.shutdown()
    with pytest.raises(SiddhiAppCreationError, match="string"):
        SiddhiManager().create_siddhi_app_runtime(
            "@app:engine('device') "
            + FILTER.format(flt="str:upper(sym) == 'A'"))
    # a string-typed filter on the keyed ring: a trace-time type
    # incompatibility, which still routes to the grouped-agg kernel
    rt = SiddhiManager().create_siddhi_app_runtime(
        KEYED_WAGG.format(flt="sym == 'a'"))
    pr = rt.partition_runtimes[0]
    assert pr.device_mode
    assert type(pr.device_query_runtimes["q"].device_runtime).__name__ \
        == "DeviceGroupedAggRuntime"
    rt.shutdown()


# ------------------------------------------------ a cache that can be placed

_CACHE_CHILD = """
import sys
sys.path.insert(0, {repo!r})
import jax.numpy as jnp
from siddhi_tpu.plan.shapes import shape_registry
f = shape_registry().jit("test.cache_place", {{"n": {n}}},
                         lambda x: x * {n} + 1)
f(jnp.arange(8.0))
import json, jax
from siddhi_tpu.plan.shapes import configure_compile_cache
print(json.dumps(configure_compile_cache()))
"""


def _cache_child(n, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    res = subprocess.run(
        [sys.executable, "-c", _CACHE_CHILD.format(repo=REPO, n=n)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_cache_lands_where_jax_is_told(tmp_path):
    d = str(tmp_path / "placed")
    state = _cache_child(3, {"JAX_COMPILATION_CACHE_DIR": d})
    assert state == {"configured": True, "enabled": True, "dir": d}
    assert os.listdir(d), "nothing cached where JAX was told to cache"


def test_cache_defaults_to_the_checkout():
    assert DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    before = set(os.listdir(DEFAULT_CACHE_DIR)) \
        if os.path.isdir(DEFAULT_CACHE_DIR) else set()
    # a multiplier no earlier run compiled, so this run must write
    n = 1 + int.from_bytes(os.urandom(3), "big")
    state = _cache_child(n, {})
    assert state == {"configured": True, "enabled": True,
                     "dir": DEFAULT_CACHE_DIR}
    assert set(os.listdir(DEFAULT_CACHE_DIR)) - before


def test_no_mkdtemp_made_cache_remains():
    for path in [
            os.path.join(r, f) for r, _d, fs in
            os.walk(os.path.join(REPO, "siddhi_tpu")) for f in fs
            if f.endswith(".py")]:
        with open(path) as f:
            assert "mkdtemp" not in f.read(), path


# ------------------------------------------------------- the native packer

def test_native_packer_builds_itself_and_warns_when_it_cannot(
        monkeypatch, tmp_path, caplog):
    from siddhi_tpu import native_ext
    assert native_ext.native_status()["loaded"]      # this checkout has it
    fresh = {"tried": False, "built": False, "loaded": False, "error": ""}
    # missing library, source present: built on first use
    monkeypatch.setattr(native_ext, "_SO", str(tmp_path / "_native.so"))
    monkeypatch.setattr(native_ext, "_STATUS", dict(fresh))
    monkeypatch.setattr(native_ext, "_LIB", None)
    assert native_ext.native_status() == {"built": True, "loaded": True,
                                          "error": ""}
    rows, counts, t = native_ext.assign_rows(
        np.array([0, 1, 0, 2, 0], np.int32), 3)
    assert rows.tolist() == [0, 0, 1, 0, 2] and t == 3
    # no library and no source: the Python loop, announced once, loudly
    monkeypatch.setattr(native_ext, "_SO", str(tmp_path / "absent.so"))
    monkeypatch.setattr(native_ext, "_SRC", str(tmp_path / "absent.cpp"))
    monkeypatch.setattr(native_ext, "_STATUS", dict(fresh))
    monkeypatch.setattr(native_ext, "_LIB", None)
    with caplog.at_level(logging.WARNING, logger="siddhi_tpu.native_ext"):
        rows2, _c, _t = native_ext.assign_rows(
            np.array([0, 1, 0, 2, 0], np.int32), 3)
        native_ext.assign_rows(np.array([1], np.int32), 3)
    assert rows2.tolist() == rows.tolist()
    warned = [r for r in caplog.records if "per-event" in r.getMessage()]
    assert len(warned) == 1
