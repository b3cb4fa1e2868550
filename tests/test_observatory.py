"""Quality & efficiency observatory (DESIGN.md §17): online recall probes,
compiled-program roofline profiles, and the bench regression sentinel.

Pins the PR's acceptance invariants:
  * probe sampling is a pure function of (seed, ordinal) — the same seed
    over the same traffic reproduces the same probe set across restarts;
  * the windowed Wilson estimate tracks exact recall@k within ±0.05 on a
    seeded synthetic run, and probing changes NO served result ids
    (observe-only, bit-exact);
  * filtered and live queries are judged against the RIGHT sub-corpus
    (predicate-passing rows; alive logical rows via slot_to_logical);
  * a sustained recall breach walks server health to DEGRADED and counts
    quality_degraded_total; recovery returns to SERVING;
  * capture_search profiles every registry engine's whole batched search
    as one compiled program with nonzero flops/bytes and exports
    roofline_* gauges;
  * regress.py rejects unstamped artifacts, passes clean on an exact
    self-comparison, and exits nonzero on an injected 20% p50 regression;
  * migrate_legacy stamps bare-list artifacts in place, folds the orphan
    aggregate into missing per-bench files, and never clobbers a stamped
    artifact.
"""
import json
import math
import os

import numpy as np
import pytest

from benchmarks import migrate_legacy, regress
from repro.core import index as index_lib
from repro.core import probes as probes_lib
from repro.core import profile as profile_lib
from repro.core import scan as scan_lib
from repro.core import telemetry as telem
from repro.dist import roofline
from repro.launch.serve import SearchServer

N, D, K = 256, 16, 10


@pytest.fixture(autouse=True)
def _clean_registry():
    """Telemetry and the profile registry are process-global: every test
    starts and ends disabled + zeroed."""
    telem.disable()
    telem.reset()
    profile_lib.reset()
    yield
    telem.disable()
    telem.reset()
    profile_lib.reset()


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(N, D)).astype(np.float32)
    Q = X[:64] + 0.01 * rng.normal(size=(64, D)).astype(np.float32)
    return X, Q


# ---------------------------------------------------------------------------
# probe primitives
# ---------------------------------------------------------------------------

def test_sample_draw_is_pure_and_seed_dependent():
    a = [probes_lib.sample_draw(7, i) for i in range(100)]
    b = [probes_lib.sample_draw(7, i) for i in range(100)]
    c = [probes_lib.sample_draw(8, i) for i in range(100)]
    assert a == b
    assert a != c
    assert all(0.0 <= x < 1.0 for x in a)


def test_sampled_mask_restart_determinism():
    """The same seed over the same ordinal stream reproduces the same
    probe set, regardless of how the stream is chunked (a restart replays
    the ordinals, not the batches)."""
    whole = probes_lib.sampled_mask(3, 0.25, 0, 300)
    chunked = np.concatenate([
        probes_lib.sampled_mask(3, 0.25, 0, 100),
        probes_lib.sampled_mask(3, 0.25, 100, 137),
        probes_lib.sampled_mask(3, 0.25, 237, 63),
    ])
    np.testing.assert_array_equal(whole, chunked)
    # rate is honored in expectation (binomial, wide slack)
    assert 0.10 < whole.mean() < 0.45


def test_wilson_interval_brackets_and_degenerates():
    p, lo, hi = probes_lib.wilson_interval(90, 100)
    assert lo < p == 0.9 < hi
    assert 0.0 <= lo and hi <= 1.0
    # no trials: maximally uncertain, never a division crash
    assert probes_lib.wilson_interval(0, 0) == (0.0, 0.0, 1.0)
    # p = 1 stays inside [0, 1] and the interval still has width
    p1, lo1, hi1 = probes_lib.wilson_interval(50, 50)
    assert p1 == 1.0 and hi1 == 1.0 and lo1 < 1.0


def test_count_hits_subcorpus_trials():
    """trials = number of VALID ground-truth ids: a perfect answer over a
    2-row sub-corpus scores 2/2, not 2/k."""
    served = np.array([[5, 9, -1], [1, 2, 3]])
    truth = np.array([[9, 5, -1], [7, 8, -1]])
    hits, trials = probes_lib.count_hits(served, truth)
    np.testing.assert_array_equal(hits, [2, 0])
    np.testing.assert_array_equal(trials, [2, 2])


def test_probe_config_sugar_and_validation():
    assert probes_lib.ProbeConfig.from_cfg(0.05).rate == 0.05
    assert probes_lib.ProbeConfig.from_cfg({"rate": 0.1, "k": 5}).k == 5
    with pytest.raises(ValueError):
        probes_lib.ProbeConfig(rate=1.5)
    with pytest.raises(ValueError):
        probes_lib.ProbeConfig(slo_floor=0.0)
    with pytest.raises(TypeError):
        probes_lib.ProbeConfig.from_cfg("0.1")


def test_view_key_distinguishes_filters():
    k0 = probes_lib.view_key(None)
    k1 = probes_lib.view_key({"category": {"isin": ["a"]}})
    k2 = probes_lib.view_key({"category": {"isin": ["b"]}})
    k3 = probes_lib.view_key(np.array([True, False, True]))
    assert k0 is None
    assert len({k1, k2, k3}) == 3
    # dict key order must not matter
    assert probes_lib.view_key({"a": 1, "b": 2}) == \
        probes_lib.view_key({"b": 2, "a": 1})


# ---------------------------------------------------------------------------
# server-integrated probing
# ---------------------------------------------------------------------------

def _serve(server, Q, k=K, batch=16):
    outs = []
    for i in range(0, len(Q), batch):
        outs.append(server.query(Q[i:i + batch], k=k))
    return np.concatenate([np.asarray(r.idx) for r in outs], axis=0)


def test_probe_estimate_tracks_exact_recall_and_is_bit_exact(data):
    """The headline acceptance: 1%-class sampled probing estimates
    recall within ±0.05 of the exact value, without changing a single
    served id."""
    X, Q = data
    Qm = np.concatenate([Q] * 10, axis=0)  # 640 queries
    plain = SearchServer(X, engine="ivf_flat", cfg={"budget": 96})
    probed = SearchServer(X, engine="ivf_flat", cfg={"budget": 96},
                          probe={"rate": 0.5, "k": K, "seed": 1})
    idx_plain = _serve(plain, Qm)
    idx_probe = _serve(probed, Qm)
    np.testing.assert_array_equal(idx_plain, idx_probe)  # observe-only

    exact = index_lib.build("brute", X, {}).search(Qm, k=K)
    hits, trials = probes_lib.count_hits(
        np.asarray(idx_plain), np.asarray(exact.idx))
    exact_recall = hits.sum() / trials.sum()

    q = probed.stats()["quality"]
    assert q["probed"] > 100  # rate 0.5 over 640 queries
    assert abs(q["recall_estimate"] - exact_recall) <= 0.05
    assert q["ci_low"] <= q["recall_estimate"] <= q["ci_high"]


def test_probe_sampling_identical_across_server_restarts(data):
    """Two servers with the same probe seed over the same traffic probe
    the same query ordinals (restart reproducibility at the server
    level)."""
    X, Q = data
    a = SearchServer(X, engine="brute",
                     probe={"rate": 0.3, "seed": 9, "flush_at": 4})
    b = SearchServer(X, engine="brute",
                     probe={"rate": 0.3, "seed": 9, "flush_at": 4})
    _serve(a, Q)
    _serve(b, Q)
    sa, sb = a.stats()["quality"], b.stats()["quality"]
    assert sa["seen"] == sb["seen"] == len(Q)
    assert sa["probed"] == sb["probed"] > 0
    # and the estimator saw identical outcomes, not just identical counts
    assert sa["recall_estimate"] == sb["recall_estimate"]


def test_probe_filtered_ground_truth(data):
    """Filtered queries are judged against the predicate-passing rows:
    recall stays ~1 for brute even though the filtered answer set would
    score near zero against unfiltered ground truth."""
    X, Q = data
    attrs = {"category": np.array(["even", "odd"])[np.arange(N) % 2]}
    server = SearchServer(X, engine="brute", attrs=attrs,
                          probe={"rate": 1.0, "k": K, "flush_at": 4})
    flt = {"category": {"isin": ["even"]}}
    for i in range(0, len(Q), 16):
        server.query(Q[i:i + 16], k=K, filter=flt)
    q = server.stats()["quality"]
    assert q["probed"] == len(Q)
    assert q["recall_estimate"] > 0.95


def test_probe_live_ground_truth(data):
    """After churn (upserts + deletes), probes judge against the alive
    logical corpus with served slot ids mapped through slot_to_logical —
    a frozen-corpus oracle would misscore every post-churn answer."""
    X, Q = data
    server = SearchServer(X, engine="brute", live=True, delta_cap=64,
                          probe={"rate": 1.0, "k": K, "flush_at": 4})
    rng = np.random.default_rng(5)
    new_ids = server.upsert(rng.normal(size=(16, D)).astype(np.float32))
    server.delete(new_ids[:8])
    server.delete(np.arange(8))  # tombstone frozen rows too
    for i in range(0, len(Q), 16):
        server.query(Q[i:i + 16], k=K)
    q = server.stats()["quality"]
    assert q["probed"] == len(Q)
    assert q["recall_estimate"] > 0.95


def test_probe_slo_breach_walks_health_to_degraded(data):
    """A confidently-bad window (Wilson upper bound under the floor) is a
    quality breach: health DEGRADED, quality_degraded_total counted,
    stats()['quality'] carries the breach."""
    X, Q = data
    telem.enable()
    # starved budget => genuinely low recall; floor set impossibly high
    server = SearchServer(X, engine="ivf_flat",
                          cfg={"budget": 8, "num_clusters": 32},
                          probe={"rate": 1.0, "k": K, "flush_at": 4,
                                 "slo_floor": 0.999, "slo_min_samples": 16})
    Qm = np.concatenate([Q] * 2, axis=0)
    for i in range(0, len(Qm), 16):
        server.query(Qm[i:i + 16], k=K)
    s = server.stats()
    assert s["quality"]["breached"] is True
    assert s["quality"]["breaches"] >= 1
    assert s["health"] == "DEGRADED"
    assert server.fault_counters["quality_breaches"] >= 1
    assert telem.counter_total("quality_degraded_total") >= 1
    # the exposition carries the probe gauges the CI smoke scrapes
    text = telem.metrics_text()
    assert "recall_estimate{" in text
    assert "probe_total" in text


def test_probe_swap_resets_window(data):
    """Hot-swapping engines must not blend one engine's probe window into
    the next engine's estimate."""
    X, Q = data
    server = SearchServer(X, engine="brute", probe={"rate": 1.0, "flush_at": 4})
    _serve(server, Q)
    assert server.stats()["quality"]["probed"] == len(Q)
    server.swap("ivf_flat", cfg={"budget": 96})
    q = server.stats()["quality"]
    assert q["probed"] == 0 and q["seen"] == 0


# ---------------------------------------------------------------------------
# roofline profiles
# ---------------------------------------------------------------------------

def test_capture_jit_topk_scan_profile(data):
    import jax

    X, Q = data
    fn = jax.jit(lambda Q, Y: scan_lib.topk_scan(Q, Y, k=8, metric="euclidean",
                                                 impl="jnp"))
    telem.enable()
    prof = profile_lib.capture_jit("topk:test", fn, Q, X,
                                   labels={"n": N, "k": 8})
    assert prof.flops > 0 and prof.hbm_bytes > 0
    assert prof.t_predicted_s > 0 and prof.t_measured_s > 0
    # a CPU time divided into a TPU prediction is no share of any peak
    assert prof.pct_of_peak is None
    assert prof.peak_kind == roofline.TARGET_KIND
    assert prof.dominant in ("compute", "memory", "collective")
    text = telem.metrics_text()
    assert "roofline_predicted_s{" in text
    assert "roofline_pct_of_peak{" not in text
    # re-capture returns the cached profile (no recompilation)
    again = profile_lib.capture_jit("topk:test", fn, Q, X,
                                    labels={"n": N, "k": 8})
    assert again is prof


@pytest.mark.parametrize("engine,cfg", [
    ("brute", {}),
    ("ivf_flat", {"budget": 96}),
    ("infinity", {"q": math.inf, "train_steps": 10, "proj_sample": 64,
                  "budget": 128, "rerank": 32}),
])
def test_capture_search_profiles_registry_engines(data, engine, cfg):
    """Every registry engine's whole batched search traces into ONE
    compiled program with a nonzero roofline, telemetry on throughout
    (the capture suspends it only around tracing)."""
    X, Q = data
    telem.enable()
    eng = index_lib.build(engine, X, cfg)
    prof = profile_lib.capture_search(eng, Q[:16], k=K, engine=engine)
    assert prof.name == f"search:{engine}"
    assert prof.labels["engine"] == engine
    assert prof.flops > 0 and prof.hbm_bytes > 0
    assert prof.t_measured_s > 0
    assert telem.enabled()  # restored after tracing
    assert profile_lib.profiles(f"search:{engine}") == [prof]


def test_server_capture_roofline(data):
    X, _ = data
    server = SearchServer(X, engine="brute")
    out = server.capture_roofline(batch=16, k=K)
    (name, blk), = out.items()
    assert name == "search:brute"
    assert blk["flops"] > 0 and blk["t_predicted_s"] > 0
    assert blk.get("pct_of_peak") is None


def test_v5e_device_kind_resolves_to_its_published_peaks():
    chip = roofline.peaks("TPU v5 lite")
    assert chip.bf16_flops == 197e12 and chip.int8_ops == 393e12
    assert chip.hbm_bytes_s == 819e9 and chip.ici_bytes_s == 1600e9 / 8
    assert "TPU v5e" in chip.source

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    assert roofline.device_peaks(Dev()) is chip


def test_unknown_tpu_kind_raises_and_cpu_has_no_peaks():
    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    with pytest.raises(KeyError, match="TPU v99"):
        roofline.device_peaks(Dev())

    class Cpu:
        platform, device_kind = "cpu", "cpu"

    assert roofline.device_peaks(Cpu()) is None


# ---------------------------------------------------------------------------
# regression sentinel + legacy migration
# ---------------------------------------------------------------------------

def _stamped(rows):
    from benchmarks.common import env_stamp

    return {"meta": env_stamp(), "rows": rows}


SERVING_ROWS = [
    {"engine": "brute", "shards": 1, "k": 10, "n": 2048,
     "p50_ms": 2.5, "p99_ms": 4.0, "qps": 25000.0,
     "mean_comparisons": 2048.0, "recall@k": 1.0},
    {"engine": "ivf_flat", "shards": 1, "k": 10, "n": 2048,
     "p50_ms": 1.2, "p99_ms": 2.0, "qps": 50000.0,
     "mean_comparisons": 300.0, "recall@k": 0.97},
]


def _bundle(tmp_path, name, rows):
    path = str(tmp_path / name)
    regress.save_bundle(path, {"serving": ({}, json.loads(json.dumps(rows)))})
    return path


def test_load_stamped_rejects_legacy_formats(tmp_path):
    bare = tmp_path / "BENCH_bare.json"
    bare.write_text(json.dumps([{"p50_ms": 1.0}]))
    with pytest.raises(regress.UnstampedArtifact, match="migrate_legacy"):
        regress.load_stamped(str(bare))
    nostamp = tmp_path / "BENCH_nostamp.json"
    nostamp.write_text(json.dumps({"meta": {}, "rows": []}))
    with pytest.raises(regress.UnstampedArtifact, match="git_commit"):
        regress.load_stamped(str(nostamp))
    ok = tmp_path / "BENCH_ok.json"
    ok.write_text(json.dumps(_stamped([{"p50_ms": 1.0}])))
    meta, rows = regress.load_stamped(str(ok))
    assert "git_commit" in meta and rows == [{"p50_ms": 1.0}]


def test_regress_clean_self_comparison_exits_zero(tmp_path, capsys):
    b = _bundle(tmp_path, "base.json", SERVING_ROWS)
    report = str(tmp_path / "R.md")
    rc = regress.main(["--baseline", b, "--fresh", b, "--report", report])
    assert rc == 0
    assert os.path.exists(report)
    assert "regressions: **0**" in open(report).read()


def test_regress_injected_p50_regression_exits_nonzero(tmp_path):
    """The acceptance self-test: a synthetic 20% p50 regression on one
    engine trips the sentinel, and only on that engine's rows."""
    b = _bundle(tmp_path, "base.json", SERVING_ROWS)
    report = str(tmp_path / "R.md")
    rc = regress.main(["--baseline", b, "--fresh", b,
                       "--inject", "p50_ms=1.2", "--inject-match",
                       "engine=brute", "--report", report])
    assert rc == 1
    txt = open(report).read()
    assert "REGRESSION" in txt
    # the ivf_flat rows were untouched and must not be flagged
    for line in txt.splitlines():
        if "REGRESSION" in line and "|" in line:
            assert "ivf_flat" not in line


def test_regress_recall_floor_is_absolute(tmp_path):
    fresh_rows = json.loads(json.dumps(SERVING_ROWS))
    fresh_rows[1]["recall@k"] = 0.90  # 0.97 - 0.07 < floor tolerance 0.05
    b = _bundle(tmp_path, "base.json", SERVING_ROWS)
    f = _bundle(tmp_path, "fresh.json", fresh_rows)
    rc = regress.main(["--baseline", b, "--fresh", f,
                       "--report", str(tmp_path / "R.md")])
    assert rc == 1


def test_regress_faster_machine_is_not_a_regression(tmp_path):
    """Rows absolutely better than baseline never flag, even when the
    suite-median speedup is heterogeneous (the normalizer is clamped at
    >= 1 for the hard gate)."""
    fresh_rows = json.loads(json.dumps(SERVING_ROWS))
    fresh_rows[0]["p50_ms"] = 2.0    # 1.25x faster
    fresh_rows[0]["qps"] = 31000.0
    fresh_rows[1]["p50_ms"] = 0.4    # 3x faster
    fresh_rows[1]["qps"] = 150000.0
    b = _bundle(tmp_path, "base.json", SERVING_ROWS)
    f = _bundle(tmp_path, "fresh.json", fresh_rows)
    rc = regress.main(["--baseline", b, "--fresh", f,
                       "--report", str(tmp_path / "R.md")])
    assert rc == 0


def test_migrate_legacy_stamps_and_folds(tmp_path):
    d = str(tmp_path)
    # a bare-list per-bench artifact -> stamped in place
    bare = tmp_path / "BENCH_topk.json"
    bare.write_text(json.dumps([{"n": 4096, "t_scan_jnp_s": 0.1}]))
    # a stamped artifact that must NOT be clobbered
    stamped = tmp_path / "BENCH_serving.json"
    stamped.write_text(json.dumps(_stamped([{"engine": "brute"}])))
    keep_meta = json.load(open(stamped))["meta"]
    # the orphan aggregate: one key targets the stamped file (dropped),
    # one targets a missing file (folded), one is unknown (skipped)
    orphan = tmp_path / "bench_results.json"
    orphan.write_text(json.dumps({
        "serving": [{"engine": "old"}],
        "infinity": [{"q": "inf", "p50_ms": 9.0}],
        "mystery": [{"x": 1}],
    }))

    actions = migrate_legacy.migrate(d, verbose=False)
    assert not (tmp_path / "bench_results.json").exists()
    topk = json.load(open(tmp_path / "BENCH_topk.json"))
    assert "meta" in topk and topk["rows"] == [{"n": 4096, "t_scan_jnp_s": 0.1}]
    assert "migrated_from" in topk["meta"]
    assert json.load(open(stamped))["meta"] == keep_meta  # untouched
    inf = json.load(open(tmp_path / "BENCH_infinity.json"))
    assert inf["rows"] == [{"q": "inf", "p50_ms": 9.0}]
    assert any("mystery" in a for a in actions)
    # after migration every artifact loads under the sentinel's validator
    for f in ("BENCH_topk.json", "BENCH_serving.json", "BENCH_infinity.json"):
        regress.load_stamped(str(tmp_path / f))


def test_committed_artifacts_are_stamped():
    """The repo's own trajectory must satisfy the sentinel's --check."""
    rc = regress.main(["--check"])
    assert rc == 0
