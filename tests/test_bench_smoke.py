"""bench.py is part of the tested surface (round 6).

The bench script itself once had no tier-1 coverage, so a bench-only
regression could sit undetected until the next device round.  Subprocess
checks close that:

  * `bench.py --smoke` (CPU-pinned, one tiny block per phase, seconds)
    must exit 0 and emit valid JSON with the per-phase fields, including
    the NFA B-sweep with equal match counts across B;
  * a full run (`bench.py`, no `--smoke`) with an unreachable backend, or
    with only the CPU, must exit non-zero: a measurement path that finds
    no chip fails instead of skipping or timing the CPU.
"""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench.py")


def _run(args, env_extra=None, timeout=560):
    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, BENCH] + args,
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=ROOT)


def test_bench_smoke_runs_clean():
    res = _run(["--smoke"])
    assert res.returncode == 0, res.stdout + res.stderr
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["smoke"] is True and out["platform"] == "cpu"
    assert out["gate_matches"] > 0
    assert out["gate_dropped"] == 0
    assert out["engine_matches_delivered"] > 0
    sweep = out["b_sweep"]
    assert [r["batch_b"] for r in sweep] == [1, 2, 4]
    # bit-identical match semantics across B, asserted inside the sweep
    # and visible here
    assert len({r["matches_counted"] for r in sweep}) == 1
    # ticks really drop T -> ceil(T/B)
    for r in sweep:
        assert r["scan_ticks_per_block"] == -(-8 // r["batch_b"])
    # dispatch consolidation (round 7): the tiny C=2-chunk bank really
    # drops to ONE measured device dispatch per block, at equal matches
    dsm = out["d_sweep_smoke"]
    assert dsm["sequential"]["dispatches_per_block"] == 2
    assert dsm["stacked"]["dispatches_per_block"] == 1
    assert dsm["stacked"]["matches"] == dsm["sequential"]["matches"] > 0
    # cross-tenant super-dispatch (round 14): 2 heterogeneous tenant
    # apps share one bucket and one gang launch per ingest wall — fewer
    # dispatches than the SIDDHI_TPU_XTENANT=0 run, bit-identical
    # matches asserted inside bench_mtenant itself
    msm = out["mtenant_smoke"]
    assert msm["n_apps"] == 2 and msm["tenants"] == 2
    assert msm["buckets"] >= 1
    assert msm["matches"] > 0
    assert msm["packed_dispatches_per_block"] < \
        msm["unpacked_dispatches_per_block"]
    # partition-axis shard-out (round 15): 1/2/4-shard fans over the
    # same keyed feed emit bit-identical rows (parity asserted inside
    # bench_shardscale), every key owned by exactly one shard, FNV
    # ownership balanced
    ssm = out["shardscale_smoke"]
    assert ssm["keys"] == 512
    assert ssm["parity_rows"] > 0
    assert len(ssm["shard_keys"]) == 4
    assert sum(ssm["shard_keys"]) == 512
    assert 1.0 <= ssm["max_imbalance"] < 1.5
    # ingest armor (round 9): SHED_OLDEST under a wedged consumer, with
    # exact accounting asserted inside the smoke and visible here
    osm = out["overload_smoke"]
    assert osm["admitted"] == 200
    assert osm["shed"] > 0
    assert osm["admitted"] == osm["delivered"] + osm["shed"]
    # host rim (round 11): the columnar ingest -> match -> inMemory-sink
    # run materialized ZERO per-event Event objects, while the legacy
    # per-event callback run over the same feed did materialize — both
    # asserted inside the smoke and visible here
    rsm = out["rim_smoke"]
    assert rsm["sink_rows"] > 0
    assert rsm["columnar_materialized"] == 0
    assert rsm["legacy_materialized"] > 0
    prof = out["kernel_profile"]
    assert prof["nfa.bank_step"]["scan_ticks"] > 0
    assert prof["nfa.bank_step"]["dispatch_count"] > 0
    # flight recorder + device telemetry (round 10): ring populated by
    # the smoke's own ingest, on-demand bundle round-tripped through
    # REST, and the always-on recorder's per-block overhead bounded
    # (asserted < 5% inside the smoke itself)
    fsm = out["flight_smoke"]
    assert fsm["ring_blocks"] > 0
    assert fsm["bundle_id"].startswith("inc-")
    assert fsm["bundle_ring_blocks"] > 0
    assert fsm["telemetry_gate_pass"] > 0
    assert 0.0 <= fsm["overhead_pct"] < 5.0
    # latency ledger (round 12): waterfall stage-sum reconciles against
    # the independent e2e wall clock, a forced @app:slo breach round-trips
    # an SLO001 bundle with waterfall evidence, and the always-on ledger's
    # per-block overhead stays bounded (asserted < 5% inside the smoke)
    lsm = out["ledger_smoke"]
    assert 0.3 <= lsm["waterfall_coverage_p50"] <= 2.5
    assert lsm["waterfall_attributed_p50_ms"] > 0
    assert lsm["slo_bundle_id"].startswith("inc-")
    assert lsm["slo_bundle_code"] == "SLO001"
    assert lsm["slo_waterfall_stages"] > 0
    assert 0.0 <= lsm["overhead_pct"] < 5.0
    # compile observatory (round 16): a subprocess restart against the
    # same persistent cache dir hits instead of recompiling, the shape-
    # class signatures derived in both processes are identical, and the
    # match payloads are bit-identical (parity asserted inside the smoke)
    csm = out["coldstart_smoke"]
    assert csm["cold_ttfm_s"] > csm["warm_ttfm_s"] > 0
    assert csm["warm_cache_hits"] > 0
    assert csm["cold_cache_misses"] > 0
    assert csm["signatures"]
    assert any(s.startswith("filter.program[") for s in csm["signatures"])
    assert csm["parity_digest"]
    # numeric safety (round 18): the static verifier fired on the
    # constructed overflow app, samples/ are NS-clean, the armed
    # NUMGUARD run tripped the device sentinel plane at bit-identical
    # outputs, and the sentinel ingest overhead stays bounded (the < 5%
    # / 50 ms noise-floor bound is asserted inside the smoke itself)
    nsm = out["numeric_smoke"]
    assert "NS005" in nsm["static_codes"]
    assert nsm["sample_findings_total"] == 0
    assert nsm["sentinel_trips"] > 0
    assert nsm["overhead_pct"] >= 0.0
    # device selection tail (round 19): having + order-by + limit
    # compiled into the egress kernel — row parity vs the host
    # QuerySelector and the device routing are asserted inside
    # bench_select itself; here we pin the artifact shape
    ssel = out["select_smoke"]
    assert ssel["rows"] > 0
    assert ssel["events_per_sec"] > 0
    assert ssel["host_events_per_sec"] > 0
    assert ssel["route_sig"].startswith("h1o1l4")


def test_fail_on_p99_gate():
    """--fail-on-p99 on the waterfall phase: an impossible threshold
    must exit 1 with the FAIL line; a generous one must pass rc 0."""
    args = ["--phase", "waterfall", "--wf-blocks", "6",
            "--wf-chunk", "512"]
    env = {"JAX_PLATFORMS": "cpu"}
    res = _run(args + ["--fail-on-p99", "0.000001"], env_extra=env)
    assert res.returncode == 1, res.stdout + res.stderr
    assert "[bench] FAIL" in res.stderr
    assert "--fail-on-p99" in res.stderr
    # the phase still printed its JSON before the gate tripped
    wf = json.loads(res.stdout.strip().splitlines()[-1])
    assert wf["e2e_p99_ms"] > 0

    res = _run(args + ["--fail-on-p99", "1e9"], env_extra=env)
    assert res.returncode == 0, res.stdout + res.stderr
    wf = json.loads(res.stdout.strip().splitlines()[-1])
    assert wf["waterfall"] and wf["coverage_p50"] > 0


def test_fail_on_imbalance_gate():
    """--fail-on-imbalance on the shardscale phase: the max/mean key
    ratio is >= 1 by construction, so a sub-1 threshold must exit 1
    with the FAIL line; a generous one must pass rc 0."""
    args = ["--phase", "shardscale", "--sc-keys", "1024",
            "--sc-shards", "1,4"]
    env = {"JAX_PLATFORMS": "cpu", "SIDDHI_TPU_MESH": "off"}
    res = _run(args + ["--fail-on-imbalance", "0.99"], env_extra=env)
    assert res.returncode == 1, res.stdout + res.stderr
    assert "[bench] FAIL" in res.stderr
    assert "--fail-on-imbalance" in res.stderr
    # the phase still printed its JSON before the gate tripped
    sc = json.loads(res.stdout.strip().splitlines()[-1])
    assert sc["shardscale_max_imbalance"] >= 1.0

    res = _run(args + ["--fail-on-imbalance", "10.0"], env_extra=env)
    assert res.returncode == 0, res.stdout + res.stderr
    sc = json.loads(res.stdout.strip().splitlines()[-1])
    row4 = next(r for r in sc["shardscale"] if r["shards"] == 4)
    assert len(row4["shard_keys"]) == 4
    assert sum(row4["shard_keys"]) == 1024


def test_fail_on_numeric_gate():
    """--fail-on-numeric: jax-free samples/ NS sweep — the shipped
    samples are clean (0 warnings), so limit 0 passes rc 0 and the
    only way to force the failure arm without dirtying samples/ is an
    impossible limit of -1."""
    env = {"JAX_PLATFORMS": "cpu"}
    res = _run(["--fail-on-numeric", "-1"], env_extra=env, timeout=120)
    assert res.returncode == 1, res.stdout + res.stderr
    assert "[bench] FAIL" in res.stderr
    assert "--fail-on-numeric" in res.stderr
    # the sweep still printed its JSON before the gate tripped
    ns = json.loads(res.stdout.strip().splitlines()[-1])
    assert ns["unit"] == "warnings" and ns["value"] == 0

    res = _run(["--fail-on-numeric", "0"], env_extra=env, timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr
    ns = json.loads(res.stdout.strip().splitlines()[-1])
    assert ns["value"] == 0 and ns["per_file"] == {}


def test_bench_fails_on_unreachable_backend():
    # a platform name jax cannot initialize: the first phase child fails
    # on it and the full run exits non-zero — a measurement path that
    # finds no chip must not report success (inverted in PR 21; the old
    # behaviour was a structured skip and exit 0)
    res = _run([], env_extra={"JAX_PLATFORMS": "no_such_backend"},
               timeout=300)
    assert res.returncode != 0, res.stdout + res.stderr
    assert "skipped" not in res.stdout
    assert "no_such_backend" in res.stderr


def test_bench_full_run_refuses_the_cpu():
    # with only the CPU backend the gate child names the platform it
    # found and the run exits non-zero before measuring anything
    res = _run([], env_extra={"JAX_PLATFORMS": "cpu"}, timeout=300)
    assert res.returncode != 0, res.stdout + res.stderr
    assert "'platform': 'cpu'" in res.stderr
