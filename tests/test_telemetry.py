"""Search telemetry subsystem (DESIGN.md §16): registry, spans on the
profiler's clock, Prometheus exposition, and the instrumented query path.

Pins the PR's acceptance invariants:
  * a beam query's per-stage comparison counters (traversal /
    centroid_rank / bucket_scan, threaded out of the jitted program as
    extra scalar outputs) sum exactly to the engine-reported comparisons,
    with the rerank stage on top at the engine level;
  * a span lands in a profiler trace on the ``/host:CPU`` plane, with its
    labels as stats, and an instrumented beam query shows its stages
    there; the jitted stages carry their names into the HLO metadata;
  * ``metrics_text()`` parses as Prometheus text exposition (cumulative
    ``_bucket{le=...}`` histograms + ``_sum``/``_count``);
  * enabling telemetry changes NO search result ids (bit-exact), and the
    search never waits on the device for it;
  * under injected faults the counters stay consistent — telemetry
    retries == the server's fault_counters == the chaos plan's injected
    count — and spans close (flagged) on exception paths;
  * a warm bucket compiles nothing with the spans in place;
  * ``SearchServer``'s latency record is a bounded ring: 100k appends
    hold memory flat while percentile semantics cover the window.
"""
import glob
import json
import math
import os
import re

import jax
import numpy as np
import pytest

from repro.core import baselines
from repro.core import chaos as chaos_lib
from repro.core import index as index_lib
from repro.core import telemetry as telem
from repro.core import vptree as vptree_lib
from repro.launch.serve import FaultPolicy, LatencyRing, SearchServer

N, D = 256, 16


@pytest.fixture(autouse=True)
def _clean_registry():
    """Telemetry state is process-global: every test starts and ends
    disabled + zeroed so no counters leak across the suite."""
    telem.disable()
    telem.reset()
    yield
    telem.disable()
    telem.reset()


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(N, D)).astype(np.float32)
    Q = X[:12] + 0.01
    return X, Q


@pytest.fixture(scope="module")
def infinity_engine(data):
    X, _ = data
    return index_lib.build("infinity", X, {
        "q": math.inf, "train_steps": 20, "proj_sample": 64,
        "budget": 192, "rerank": 32,
    })


def _host_spans(tmp_path, body) -> list:
    """Run ``body`` under the JAX profiler; the host-plane events it
    recorded, as ``(name, {stat: value})`` in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    evs = [ev for plane in pd.planes if plane.name == "/host:CPU"
           for line in plane.lines for ev in line.events]
    evs.sort(key=lambda ev: ev.start_ns)
    return [(ev.name, {k: str(v) for k, v in ev.stats}) for ev in evs]


def _named(spans, name) -> list:
    return [stats for n, stats in spans if n == name]


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------

def test_disabled_entry_points_are_noops():
    telem.count("c_total", 5, engine="x")
    telem.observe("h_seconds", 0.1, engine="x")
    telem.set_gauge("g", 1.0)
    with telem.span("stage_x", engine="x"):
        pass
    snap = telem.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}
    assert snap["gauges"] == {}


def test_counter_accumulates_per_label_set():
    telem.enable()
    telem.count("c_total", 2, engine="a", stage="s1")
    telem.count("c_total", 3, engine="a", stage="s1")
    telem.count("c_total", 7, engine="a", stage="s2")
    assert telem.counter_total("c_total", engine="a", stage="s1") == 5
    assert telem.counter_total("c_total", engine="a") == 12
    assert telem.counter_total("c_total") == 12


def test_metric_kind_collision_raises():
    telem.enable()
    telem.count("thing_total", 1)
    with pytest.raises(TypeError):
        telem.REGISTRY.histogram("thing_total")


def test_histogram_buckets_are_fixed_and_cumulative_in_exposition():
    telem.enable()
    for v in (2e-4, 2e-4, 3e-3, 0.7, 100.0):  # last lands in +Inf
        telem.observe("lat_seconds", v, engine="e")
    [(lbl, rec)] = telem.histogram_series("lat_seconds")
    assert lbl == {"engine": "e"}
    assert rec["count"] == 5
    assert sum(rec["buckets"]) == 5
    assert rec["buckets"][-1] == 1  # the +Inf overflow slot


def test_counter_adds_a_device_array_when_read():
    telem.enable()
    comps = jax.numpy.arange(4, dtype=jax.numpy.int32)  # sums to 6
    telem.count("c_total", comps, engine="a", stage="s1")
    telem.count("c_total", 2, engine="a", stage="s1")
    assert telem.counter_total("c_total", engine="a", stage="s1") == 8
    assert telem.counter_series("c_total") == [
        ({"engine": "a", "stage": "s1"}, 8)]


def test_span_records_histogram_and_trace_event(tmp_path):
    telem.enable()

    def body():
        with telem.span("stage_y", engine="e", q="inf"):
            pass

    spans = _host_spans(tmp_path, body)
    [(lbl, rec)] = telem.histogram_series("stage_seconds")
    assert lbl == {"engine": "e", "q": "inf", "stage": "stage_y"}
    assert rec["count"] == 1
    [stats] = _named(spans, "stage_y")
    assert stats == {"engine": "e", "q": "inf"}  # no error flag


def test_span_closes_on_exception_and_flags_error(tmp_path):
    telem.enable()

    def body():
        with pytest.raises(RuntimeError):
            with telem.span("doomed", engine="e"):
                raise RuntimeError("boom")

    spans = _host_spans(tmp_path, body)
    [(lbl, rec)] = telem.histogram_series("stage_seconds")
    assert rec["count"] == 1  # observed despite the raise
    [stats] = _named(spans, "doomed")
    assert stats["engine"] == "e" and stats["error"] == "1"


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'          # metric name
    r'(\{[a-zA-Z0-9_]+="(?:[^"\\]|\\.)*"'   # first label
    r'(,[a-zA-Z0-9_]+="(?:[^"\\]|\\.)*")*\})?'  # more labels
    r' (\S+)$'                               # value
)


def _parse_exposition(text: str):
    """Minimal text-format 0.0.4 parser: returns {name: [(labels_str, value)]}
    and raises on any malformed line — the 'parses as valid exposition'
    check without a prometheus_client dependency."""
    series: dict = {}
    typed: dict = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            typed[name] = kind
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        assert m, f"malformed exposition line: {line!r}"
        name, labels, _, value = m.groups()
        float(value)  # must be numeric
        series.setdefault(name, []).append((labels or "", float(value)))
    return series, typed


def test_metrics_text_parses_and_histograms_are_cumulative():
    telem.enable()
    telem.count("comparisons_total", 9, engine="e", stage="traversal")
    for v in (2e-4, 5e-3, 0.2):
        telem.observe("search_latency", v, engine="e")
    series, typed = _parse_exposition(telem.metrics_text())
    assert typed["comparisons_total"] == "counter"
    assert typed["search_latency"] == "histogram"
    assert series["comparisons_total"] == [('{engine="e",stage="traversal"}', 9.0)]
    buckets = [v for lbl, v in series["search_latency_bucket"]]
    assert buckets == sorted(buckets), "histogram buckets must be cumulative"
    assert 'le="+Inf"' in series["search_latency_bucket"][-1][0]
    assert buckets[-1] == 3.0
    [( _, count)] = series["search_latency_count"]
    assert count == 3.0


def test_exposition_escapes_label_values():
    telem.enable()
    telem.count("odd_total", 1, label='he said "hi"\nback\\slash')
    series, _ = _parse_exposition(telem.metrics_text())
    assert series["odd_total"][0][1] == 1.0


# ---------------------------------------------------------------------------
# beam stage counters: jit-threaded accounting (acceptance invariant)
# ---------------------------------------------------------------------------

def test_beam_stage_counters_sum_to_comparisons(infinity_engine, data):
    _, Q = data
    flat, Zf, _ = infinity_engine._flat_view()
    idx, dist, comps, stages = vptree_lib.search_beam(
        flat, np.asarray(infinity_engine.Z[:12]), q=math.inf, k=5, X=Zf,
        metric="euclidean", max_comparisons=192, with_stages=True,
    )
    assert set(stages) == {"traversal", "centroid_rank", "bucket_scan"}
    total = (np.asarray(stages["traversal"]) +
             np.asarray(stages["centroid_rank"]) +
             np.asarray(stages["bucket_scan"]))
    np.testing.assert_array_equal(total, np.asarray(comps))
    assert int(np.asarray(stages["traversal"]).min()) > 0


def test_beam_default_return_signature_unchanged(infinity_engine):
    flat, Zf, _ = infinity_engine._flat_view()
    out = vptree_lib.search_beam(
        flat, np.asarray(infinity_engine.Z[:4]), q=math.inf, k=3, X=Zf,
        metric="euclidean",
    )
    assert len(out) == 3  # (idx, dist, comps) — pre-PR callers unaffected


def test_engine_counters_sum_to_reported_comparisons(infinity_engine, data,
                                                    tmp_path):
    _, Q = data
    telem.enable()
    out = {}

    def body():
        out["res"] = infinity_engine.search(Q, k=5, mode="beam")
        jax.block_until_ready(out["res"].idx)

    spans = _host_spans(tmp_path, body)
    reported = int(np.asarray(out["res"].comparisons).sum())
    counted = telem.counter_total("comparisons_total", engine="infinity")
    assert counted == reported
    # the profiler's trace of one beam query holds its three host stages
    names = [n for n, _ in spans if n in ("embed", "traversal", "rerank")]
    assert names == ["embed", "traversal", "rerank"]
    assert _named(spans, "traversal")[0]["mode"] == "beam"


def test_enabling_telemetry_is_bit_exact(infinity_engine, data):
    _, Q = data
    for mode in ("beam", "best_first"):
        off = infinity_engine.search(Q, k=5, mode=mode)
        telem.enable()
        on = infinity_engine.search(Q, k=5, mode=mode)
        telem.disable()
        np.testing.assert_array_equal(np.asarray(off.idx), np.asarray(on.idx))
        np.testing.assert_array_equal(
            np.asarray(off.comparisons), np.asarray(on.comparisons))


@pytest.mark.parametrize("mode", ["beam", "best_first", "descend"])
def test_search_never_waits_on_the_device(infinity_engine, data, monkeypatch,
                                          mode):
    """Telemetry on, the engine records its stages without one
    ``block_until_ready``: counters take the device arrays as they are."""
    _, Q = data
    telem.enable()
    waits = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waits.append(1) or real(x))
    res = infinity_engine.search(Q, k=5, mode=mode)
    assert waits == []
    assert telem.counter_total("comparisons_total", engine="infinity") == \
        int(np.asarray(res.comparisons).sum())


def _lowered(kind: str, eng) -> str:
    """HLO text with locations of one jitted stage program."""
    Zq = eng.Z[:4]
    t = eng.tree
    tree = (t.vantage, t.mu, t.left, t.right)
    if kind == "_descend_impl":
        low = vptree_lib._descend_impl.lower(tree, eng.Z, Zq,
                                             metric="euclidean", depth=t.depth)
    elif kind == "_best_first_impl":
        low = vptree_lib._best_first_impl.lower(
            tree, eng.Z, Zq, jax.numpy.int32(64), metric="euclidean",
            q=math.inf, k=3, stack_cap=2 * t.depth + 8)
    elif kind == "_beam_impl":
        flat, Zf, _ = eng._flat_view()
        low = vptree_lib._beam_impl.lower(
            (flat.mu, flat.child_in, flat.child_out, flat.rad_in,
             flat.rad_out, flat.bucket_rows, flat.perm, flat.centroids),
            Zf, Zq, metric="euclidean", q=math.inf, k=3, beam_width=4,
            bucket_cap=2, depth=flat.depth)
    else:
        low = baselines.brute_force.lower(eng.X, eng.X[:4], k=3)
    return low.as_text(debug_info=True)


@pytest.mark.parametrize("program, stage", [
    ("_descend_impl", "traversal"), ("_best_first_impl", "traversal"),
    ("_beam_impl", "traversal"), ("brute_force", "scan")])
def test_stage_names_reach_the_hlo_metadata(infinity_engine, program, stage):
    """The jitted stages trace under ``jax.named_scope(stage)``: the name
    sits in their ops' metadata, where the profiler's op events read it."""
    txt = _lowered(program, infinity_engine)
    assert f'"jit({program})/{stage}/' in txt


@pytest.mark.parametrize("engine", ["brute", "infinity"])
def test_warm_bucket_compiles_nothing_with_spans(data, engine):
    X, Q = data
    cfg = {} if engine == "brute" else {
        "q": math.inf, "train_steps": 20, "proj_sample": 64,
        "budget": 192, "rerank": 32}
    telem.enable()
    srv = SearchServer(X, engine=engine, cfg=cfg)
    srv.query(Q, k=5)  # compiles this bucket
    compiles = []

    def on_event(event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        with telem.span("caller", engine=engine):
            srv.query(Q, k=5)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []


# ---------------------------------------------------------------------------
# instrumented serving path under failure (chaos consistency)
# ---------------------------------------------------------------------------

def test_server_counters_match_fault_counters_and_chaos(data, tmp_path):
    X, Q = data
    telem.enable()
    plan = chaos_lib.FaultPlan(
        rules=[{"site": "search", "kind": "error", "start": 1, "stop": 3}])
    srv = SearchServer(X, engine="brute", cfg={}, chaos=plan,
                       policy=FaultPolicy(max_retries=3,
                                          backoff_base_s=0.001))

    def body():
        srv.query(Q, k=5)
        srv.query(Q, k=5)  # absorbs injection 1
        srv.query(Q, k=5)  # absorbs injection 2

    spans = _host_spans(tmp_path, body)
    injected = sum(plan.stats()["injected"].values())
    assert injected == 2
    assert srv.fault_counters["retries"] == injected
    assert telem.counter_total("retries_total", engine="brute") == injected
    assert telem.counter_total("faults_total", engine="brute") == injected
    assert telem.counter_total("queries_total", engine="brute") == 3 * len(Q)
    # every retried dispatch opened AND closed a span: 3 clean + 2 flagged
    dispatch = _named(spans, "dispatch")
    assert len(dispatch) == 5
    assert sum("error" in st for st in dispatch) == 2


def test_fault_storm_closes_spans_on_the_raising_path(data, tmp_path):
    X, Q = data
    telem.enable()
    plan = chaos_lib.FaultPlan(
        rules=[{"site": "search", "kind": "error", "start": 1, "stop": 50}])
    srv = SearchServer(X, engine="brute", cfg={}, chaos=plan,
                       policy=FaultPolicy(max_retries=1,
                                          backoff_base_s=0.001))

    def body():
        srv.query(Q, k=5)
        with pytest.raises(chaos_lib.TransientFault):
            srv.query(Q, k=5)
        with telem.span("after"):  # the span machinery still works
            pass

    spans = _host_spans(tmp_path, body)
    dispatch = _named(spans, "dispatch")
    # 1 clean + 2 flagged (first attempt + the exhausted retry): no span
    # leaks open even though the second query raised out of the server
    assert len(dispatch) == 3
    assert sum("error" in st for st in dispatch) == 2
    assert len(_named(spans, "after")) == 1


def test_deadline_miss_counted_consistently(data):
    X, Q = data
    telem.enable()
    srv = SearchServer(X, engine="brute", cfg={})
    srv.query(Q, k=5, budget=64, deadline_ms=1e-6)
    assert srv.fault_counters["deadline_misses"] == 1
    assert telem.counter_total("deadline_misses_total", engine="brute") == 1


def test_health_transitions_become_counters(data):
    X, _ = data
    telem.enable()
    srv = SearchServer(X, engine="brute", cfg={})
    srv._set_health("DEGRADED")
    srv._set_health("RECOVERING")
    srv._set_health("SERVING")
    assert telem.counter_total("health_transitions_total") == 3
    assert telem.counter_total(
        "health_transitions_total", **{"from": "DEGRADED"}) == 1


def test_server_jit_cache_counters_track_buckets(data):
    X, Q = data
    telem.enable()
    srv = SearchServer(X, engine="brute", cfg={})
    srv.query(Q, k=5)        # bucket 16: miss
    srv.query(Q, k=5)        # same bucket: hit
    srv.query(Q[:3], k=5)    # bucket 8: miss
    assert telem.counter_total("jit_cache_misses_total", scope="server") == 2
    assert telem.counter_total("jit_cache_hits_total", scope="server") == 1


def test_stats_carries_telemetry_tree_and_metrics_text(data):
    X, Q = data
    telem.enable()
    srv = SearchServer(X, engine="brute", cfg={})
    srv.query(Q, k=5)
    s = srv.stats()
    assert "telemetry" in s
    assert s["telemetry"]["counters"]["queries_total"]
    series, _ = _parse_exposition(srv.metrics_text())
    assert "search_latency_bucket" in series
    assert "queries_total" in series
    # disabled servers don't grow a telemetry tree
    telem.disable()
    assert "telemetry" not in srv.stats()


# ---------------------------------------------------------------------------
# bounded latency record (the _lat_s bugfix)
# ---------------------------------------------------------------------------

def test_latency_ring_memory_flat_at_100k_appends():
    ring = LatencyRing(cap=4096)
    base = ring._lat.nbytes + ring._nq.nbytes
    for i in range(100_000):
        ring.append(1e-3 + (i % 7) * 1e-4, 16)
    assert len(ring) == 4096  # window, not history
    assert ring._lat.nbytes + ring._nq.nbytes == base  # no growth, ever
    lat, nq = ring.window()
    assert lat.shape == (4096,) and nq.shape == (4096,)
    assert np.all(lat > 0) and np.all(nq == 16)


def test_latency_ring_percentiles_cover_recent_window():
    ring = LatencyRing(cap=8)
    for _ in range(100):
        ring.append(1.0, 1)  # old regime: would dominate an unbounded list
    for _ in range(8):
        ring.append(0.001, 1)  # new regime fills the whole window
    lat, _ = ring.window()
    assert float(np.percentile(lat * 1e3, 50)) == pytest.approx(1.0)


def test_server_stats_batches_count_lifetime_window_bounded(data):
    X, Q = data
    srv = SearchServer(X, engine="brute", cfg={})
    srv._lat = LatencyRing(cap=4)  # tiny window to exercise wrap
    for _ in range(9):
        srv.query(Q, k=5)
    s = srv.stats()
    assert s["batches"] == 9            # lifetime total survives the wrap
    assert s["window_batches"] == 4     # percentiles cover the window
    assert s["queries"] == 9 * len(Q)
    assert s["p50_ms"] > 0 and s["qps"] > 0


# ---------------------------------------------------------------------------
# bench integration
# ---------------------------------------------------------------------------

def test_write_stamped_attaches_telemetry_summary(tmp_path):
    from benchmarks.common import write_stamped

    telem.enable()
    telem.count("comparisons_total", 11, engine="e", stage="traversal")
    path = str(tmp_path / "BENCH_x.json")
    write_stamped(path, [{"a": 1}])
    doc = json.load(open(path))
    assert doc["meta"]["telemetry"]["counters"]["comparisons_total"]
    # disabled runs stay schema-identical to pre-PR artifacts
    telem.disable()
    write_stamped(path, [{"a": 1}])
    assert "telemetry" not in json.load(open(path))["meta"]


def test_stage_breakdown_reads_the_registry(infinity_engine, data):
    from benchmarks.common import stage_breakdown

    _, Q = data
    telem.enable()
    infinity_engine.search(Q, k=5, mode="beam")
    br = stage_breakdown("infinity")
    assert {"traversal", "centroid_rank", "bucket_scan", "rerank"} <= set(br)
    for stage in ("traversal", "centroid_rank", "bucket_scan", "rerank"):
        assert br[stage]["comparisons"] > 0
    # embed rides along as a pure-latency stage (no comparison counter)
    assert br.get("embed", {"comparisons": 0.0})["comparisons"] == 0.0
    telem.disable()
    assert stage_breakdown("infinity") == {}
