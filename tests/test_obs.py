"""Observability stack tests: tracer, metrics registry, Prometheus
exposition, structured log, anatomy analysis, console folding, the
trace_events artifact, and the instrumented daemon/engine paths.

Fast tier: everything in-process.  Slow tier: child-process spans
surviving the fork-server protocol round-trip over a real benchsuite
app, and cold-vs-pool span shapes over a live ZygoteFleet.
"""

import io
import json
import os
import threading
import time
import urllib.request

import pytest

from repro.api.artifacts import load_trace_events, save_trace_events
from repro.obs.anatomy import (
    UNATTRIBUTED, folded_stacks, phase_breakdown, render_report,
    top_imports,
)
from repro.obs.console import render_table, rows_from_exposition, run_top
from repro.obs.exposition import (
    CONTENT_TYPE, MetricsServer, write_metrics_textfile,
)
from repro.obs.log import Logger, configure as configure_log
from repro.obs.metrics import (
    MetricsRegistry, default_registry, histogram_quantile,
    parse_exposition, validate_exposition,
)
from repro.obs.tracing import (
    Span, Tracer, configure_tracing, get_tracer, new_id,
    spans_from_import_timer,
)


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Every test starts from disabled tracing and an empty registry."""
    configure_tracing(enabled=False)
    get_tracer().clear()
    default_registry().reset()
    yield
    configure_tracing(enabled=False)
    get_tracer().clear()
    default_registry().reset()


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_dict_roundtrip_omits_empty_fields():
    s = Span(name="x", trace_id="t", span_id="s", t_start_ms=1.2345,
             duration_ms=2.5)
    d = s.to_dict()
    assert "parent_id" not in d and "attrs" not in d
    back = Span.from_dict(d)
    assert back.name == "x" and back.duration_ms == 2.5
    s2 = Span(name="y", trace_id="t", span_id="s2", parent_id="s",
              attrs={"app": "a"})
    d2 = s2.to_dict()
    assert d2["parent_id"] == "s" and d2["attrs"] == {"app": "a"}


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    with tr.span("request", app="a") as h:
        assert not h            # falsy noop handle
        assert h.ctx() is None  # -> protocol carries no trace field
        h.set("k", "v")         # must not raise
    assert tr.snapshot() == []
    tr.add("x", trace_id="t", t_start_ms=0.0, duration_ms=1.0)
    assert tr.snapshot() == []


def test_span_nesting_sums_within_wall_time():
    tr = Tracer(enabled=True)
    with tr.span("request", app="a") as root:
        with tr.span("dispatch", ctx=root.ctx()):
            time.sleep(0.005)
        with tr.span("invoke", ctx=root.ctx()):
            time.sleep(0.002)
    spans = {s.name: s for s in tr.snapshot()}
    assert set(spans) == {"request", "dispatch", "invoke"}
    root_s = spans["request"]
    for name in ("dispatch", "invoke"):
        child = spans[name]
        assert child.trace_id == root_s.trace_id
        assert child.parent_id == root_s.span_id
        assert child.t_start_ms >= root_s.t_start_ms
        assert (child.t_start_ms + child.duration_ms
                <= root_s.t_start_ms + root_s.duration_ms + 0.001)
    assert (spans["dispatch"].duration_ms + spans["invoke"].duration_ms
            <= root_s.duration_ms + 0.001)


def test_ring_buffer_caps_and_counts_drops():
    tr = Tracer(capacity=4, enabled=True)
    for i in range(10):
        tr.add(f"s{i}", trace_id="t", t_start_ms=float(i),
               duration_ms=1.0)
    assert len(tr.snapshot()) == 4
    assert tr.dropped == 6
    assert [s.name for s in tr.snapshot()] == ["s6", "s7", "s8", "s9"]


def test_record_dicts_skips_malformed():
    tr = Tracer(enabled=True)
    tr.record_dicts([
        {"name": "ok", "trace_id": "t", "span_id": "a",
         "t_start_ms": 0.0, "duration_ms": 1.0},
        {"not": "a span"},
        None,
    ])
    assert [s.name for s in tr.snapshot()] == ["ok"]


def test_spans_from_import_timer_preserves_parent_chain():
    from repro.core.profiler.import_timer import ModuleInitRecord
    records = {
        "libA": ModuleInitRecord(name="libA", filename="<x>",
                                 self_s=0.01, cumulative_s=0.03,
                                 parent=None),
        "libA.sub": ModuleInitRecord(name="libA.sub", filename="<x>",
                                     self_s=0.02, cumulative_s=0.02,
                                     parent="libA"),
    }
    out = spans_from_import_timer(records, trace_id="t",
                                  parent_id="phase", t_start_ms=100.0)
    by_name = {d["name"]: d for d in out}
    assert by_name["import:libA"]["parent_id"] == "phase"
    assert (by_name["import:libA.sub"]["parent_id"]
            == by_name["import:libA"]["span_id"])
    assert by_name["import:libA"]["duration_ms"] == pytest.approx(30.0)
    assert by_name["import:libA"]["attrs"]["self_ms"] == pytest.approx(
        10.0)


# ---------------------------------------------------------------------------
# metrics registry + exposition
# ---------------------------------------------------------------------------

def test_counter_histogram_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", labels=("app",))
    c.labels(app="a").inc()
    c.labels(app="a").inc(2)
    with pytest.raises(ValueError):
        c.labels(app="a").inc(-1)
    with pytest.raises(ValueError):
        c.labels(bogus="x")
    g = reg.gauge("depth", "queue depth")
    g.set(5)
    g.dec(2)
    h = reg.histogram("lat_ms", "latency", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(100.0)
    snap = reg.snapshot()
    fam = {f["name"]: f for f in snap["families"]}
    assert fam["req_total"]["series"][0]["value"] == 3
    assert fam["depth"]["series"][0]["value"] == 3
    hs = fam["lat_ms"]["series"][0]
    assert hs["counts"] == [1, 1, 1] and hs["count"] == 3
    assert hs["sum"] == pytest.approx(105.5)


def test_registry_get_or_create_is_idempotent_but_kind_clashes_raise():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x", labels=("app",))
    b = reg.counter("x_total", "x", labels=("app",))
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x")
    with pytest.raises(ValueError):
        reg.counter("x_total", "x", labels=("other",))


def test_snapshot_merge_adds_counters_and_histograms():
    reg = MetricsRegistry()
    reg.counter("n_total", "n").inc(2)
    reg.histogram("h_ms", "h", buckets=(1.0,)).observe(0.5)
    reg.gauge("g", "g").set(7)
    snap = reg.snapshot()
    reg.merge_snapshot(snap)
    fam = {f["name"]: f for f in reg.snapshot()["families"]}
    assert fam["n_total"]["series"][0]["value"] == 4
    assert fam["h_ms"]["series"][0]["count"] == 2
    assert fam["g"]["series"][0]["value"] == 7  # gauges: last wins


def test_exposition_renders_valid_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("repro_requests_total", "reqs",
                labels=("app",)).labels(app="a").inc(3)
    reg.histogram("repro_wait_ms", "wait",
                  labels=("app",)).labels(app="a").observe(2.0)
    text = reg.render()
    assert validate_exposition(text) == []
    parsed = parse_exposition(text)
    assert parsed["types"]["repro_requests_total"] == "counter"
    assert parsed["types"]["repro_wait_ms"] == "histogram"
    samples = {(n, tuple(sorted(l.items()))): v
               for n, l, v in parsed["samples"]}
    assert samples[("repro_requests_total", (("app", "a"),))] == 3.0
    # cumulative buckets end with +Inf == _count
    infs = [v for n, l, v in parsed["samples"]
            if n == "repro_wait_ms_bucket" and l.get("le") == "+Inf"]
    counts = [v for n, l, v in parsed["samples"]
              if n == "repro_wait_ms_count"]
    assert infs == counts == [1.0]


def test_histogram_quantile_upper_bound_estimate():
    pairs = [(1.0, 0.0), (10.0, 9.0), (100.0, 10.0),
             (float("inf"), 10.0)]
    assert histogram_quantile(0.5, pairs) == 10.0
    assert histogram_quantile(0.99, pairs) == 100.0
    assert histogram_quantile(0.5, []) is None


def test_metrics_server_serves_scrapes(tmp_path):
    reg = MetricsRegistry()
    reg.counter("up_total", "x").inc()
    with MetricsServer(registry=reg, port=0) as srv:
        with urllib.request.urlopen(srv.url, timeout=5) as resp:
            assert resp.headers["Content-Type"] == CONTENT_TYPE
            body = resp.read().decode()
        assert "up_total 1" in body
        health = urllib.request.urlopen(
            srv.url.replace("/metrics", "/healthz"), timeout=5)
        assert health.read().strip() == b"ok"
    path = str(tmp_path / "m.prom")
    write_metrics_textfile(path, registry=reg)
    assert validate_exposition(open(path).read()) == []


# ---------------------------------------------------------------------------
# structured log
# ---------------------------------------------------------------------------

def test_log_json_mode_and_level_threshold():
    buf = io.StringIO()
    configure_log(level="info", json_mode=True, stream=buf)
    log = Logger("test.comp")
    log.debug("dropped", n=1)
    log.info("kept", app="a", ms=1.2345)
    lines = [json.loads(l) for l in buf.getvalue().splitlines()]
    assert len(lines) == 1
    evt = lines[0]
    assert evt["event"] == "kept" and evt["component"] == "test.comp"
    assert evt["level"] == "info" and evt["app"] == "a"
    with pytest.raises(ValueError):
        configure_log(level="nope")


def test_log_text_mode_formats_key_values():
    buf = io.StringIO()
    configure_log(level="debug", json_mode=False, stream=buf)
    Logger("c").warning("thing-happened", count=3, msg="two words")
    line = buf.getvalue().strip()
    assert "WARNING" in line and "thing-happened" in line
    assert "count=3" in line and '"two words"' in line


# ---------------------------------------------------------------------------
# anatomy
# ---------------------------------------------------------------------------

def _request_trace(tid: str, wall_ms: float, children: dict) -> list:
    root = {"name": "request", "trace_id": tid, "span_id": f"{tid}-r",
            "t_start_ms": 0.0, "duration_ms": wall_ms}
    out = [root]
    t = 0.0
    for name, dur in children.items():
        out.append({"name": name, "trace_id": tid,
                    "span_id": f"{tid}-{name}",
                    "parent_id": f"{tid}-r",
                    "t_start_ms": t, "duration_ms": dur})
        t += dur
    return out


def test_phase_breakdown_attributes_and_residual_sums_to_wall():
    spans = (_request_trace("t1", 100.0,
                            {"queue_wait": 10.0, "dispatch": 80.0})
             + _request_trace("t2", 50.0, {"dispatch": 50.0}))
    out = phase_breakdown(spans)
    assert out["requests"] == 2 and out["traces"] == 2
    assert out["wall_ms_total"] == pytest.approx(150.0)
    rows = {r["phase"]: r for r in out["phases"]}
    # phase self-times + unattributed == wall, exactly
    assert sum(r["total_ms"] for r in out["phases"]) == pytest.approx(
        150.0)
    assert rows[UNATTRIBUTED]["total_ms"] == pytest.approx(10.0)
    assert out["attributed_frac"] == pytest.approx(140.0 / 150.0,
                                                   abs=1e-4)
    assert rows["dispatch"]["count"] == 2


def test_phase_breakdown_boot_traces_are_their_own_phase():
    spans = _request_trace("t1", 100.0, {"dispatch": 100.0})
    spans.append({"name": "zygote_boot", "trace_id": "b1",
                  "span_id": "b1-r", "t_start_ms": 0.0,
                  "duration_ms": 200.0})
    out = phase_breakdown(spans)
    assert out["requests"] == 1 and out["traces"] == 2
    rows = {r["phase"]: r for r in out["phases"]}
    assert rows["zygote_boot"]["total_ms"] == pytest.approx(200.0)
    # a boot trace is attributed (to its phase), not "unexplained"
    assert out["attributed_frac"] == pytest.approx(1.0)


def test_folded_stacks_self_time_paths():
    spans = _request_trace("t1", 100.0, {"dispatch": 80.0})
    spans.append({"name": "import:libA", "trace_id": "t1",
                  "span_id": "t1-i", "parent_id": "t1-dispatch",
                  "t_start_ms": 0.0, "duration_ms": 30.0})
    lines = dict(l.rsplit(" ", 1) for l in folded_stacks(spans))
    assert lines["request"] == str(20 * 1000)
    assert lines["request;dispatch"] == str(50 * 1000)
    assert lines["request;dispatch;import:libA"] == str(30 * 1000)


def test_top_imports_aggregates_by_module():
    spans = []
    for tid in ("t1", "t2"):
        spans.append({"name": "import:libA", "trace_id": tid,
                      "span_id": f"{tid}-i", "t_start_ms": 0.0,
                      "duration_ms": 40.0,
                      "attrs": {"module": "libA", "self_ms": 15.0}})
    rows = top_imports(spans, n=5)
    assert rows[0]["module"] == "libA"
    assert rows[0]["count"] == 2
    assert rows[0]["cumulative_ms"] == pytest.approx(80.0)
    assert rows[0]["self_ms"] == pytest.approx(30.0)


def test_render_report_is_printable():
    spans = _request_trace("t1", 100.0, {"dispatch": 90.0})
    text = render_report(spans, meta={"source": "test"})
    assert "cold-start anatomy" in text and "dispatch" in text


# ---------------------------------------------------------------------------
# trace_events artifact
# ---------------------------------------------------------------------------

def test_trace_events_artifact_roundtrip(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("request", app="a"):
        pass
    reg = MetricsRegistry()
    reg.counter("n_total", "n").inc()
    path = str(tmp_path / "te.json")
    save_trace_events(tr.snapshot(), path, metrics=reg.snapshot(),
                      meta={"source": "test"})
    art = load_trace_events(path)
    assert art.kind == "trace_events" and art.schema_version == 1
    assert len(art.spans) == 1 and art.spans[0]["name"] == "request"
    assert art.metrics["schema"] == "repro.metrics/1"
    assert art.meta["source"] == "test"
    raw = json.load(open(path))
    assert raw["kind"] == "trace_events"


# ---------------------------------------------------------------------------
# console
# ---------------------------------------------------------------------------

def _console_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    req = reg.counter("repro_requests_total", "r",
                      labels=("app", "outcome"))
    req.labels(app="a", outcome="queued").inc(8)
    req.labels(app="a", outcome="shed").inc(2)
    reg.counter("repro_sheds_total", "s", labels=("app", "reason")
                ).labels(app="a", reason="queue-full").inc(2)
    reg.counter("repro_served_total", "s", labels=("app",)
                ).labels(app="a").inc(8)
    dis = reg.counter("repro_dispatch_total", "d",
                      labels=("app", "path"))
    dis.labels(app="a", path="pool").inc(6)
    dis.labels(app="a", path="cold").inc(2)
    h = reg.histogram("repro_queue_wait_ms", "w", labels=("app",),
                      buckets=(1.0, 10.0))
    h.labels(app="a").observe(0.5)
    h.labels(app="a").observe(5.0)
    reg.counter("repro_base_swaps_total", "b").inc(3)
    return reg


def test_rows_from_exposition_folds_per_app():
    folded = rows_from_exposition(_console_registry().render())
    assert len(folded["apps"]) == 1
    row = folded["apps"][0]
    assert row["app"] == "a"
    assert row["requests"] == 10 and row["served"] == 8
    assert row["cold%"] == "25.0"      # 2 cold / 8 starts
    assert row["shed%"] == "20.0"      # 2 shed / 10 requests
    assert row["wait_p99_ms"] == "10.0"
    assert folded["fleet"]["base_swaps"] == 3.0
    text = render_table(folded, clock="12:00:00")
    assert "base_swaps=3" in text and "a" in text


def test_run_top_bounded_iterations_from_file(tmp_path):
    path = str(tmp_path / "m.prom")
    write_metrics_textfile(path, registry=_console_registry())
    outputs = []
    rc = run_top(path, interval_s=0.0, iterations=2, clear=False,
                 write=outputs.append)
    assert rc == 0 and len(outputs) == 2
    assert run_top(str(tmp_path / "missing.prom"), iterations=1,
                   write=outputs.append) == 1


# ---------------------------------------------------------------------------
# instrumented daemon / engine (fast tier, in-process)
# ---------------------------------------------------------------------------

def _sim_daemon(apps=("a", "b")):
    from repro.pool import (
        AppProfile, FleetDaemon, FleetManager, IdleTimeoutPolicy,
        QueueConfig, SimFleetBackend,
    )
    profiles = {a: AppProfile(app=a, cold_init_ms=400.0,
                              warm_init_ms=20.0, invoke_ms=30.0,
                              rss_mb=100.0) for a in apps}
    manager = FleetManager(profiles, IdleTimeoutPolicy(timeout_s=60.0),
                           budget_mb=2048.0,
                           queue=QueueConfig(depth=4,
                                             max_concurrency=1))
    return FleetDaemon(SimFleetBackend(manager))


def test_sim_daemon_emits_request_spans_and_counters():
    from repro.pool.trace import Request, Trace
    configure_tracing(enabled=True)
    d = _sim_daemon()
    d.start("t")
    reqs = [Request(t=float(i), app="a") for i in range(5)]
    payload = None
    try:
        for r in reqs:
            d.submit(r)
    finally:
        payload = d.shutdown(end_t=10.0)
    spans = get_tracer().snapshot()
    assert sum(1 for s in spans if s.name == "request") == 5
    snap = default_registry().snapshot()
    fam = {f["name"]: f for f in snap["families"]}
    total = sum(s["value"]
                for s in fam["repro_requests_total"]["series"])
    assert total == 5
    assert payload["requests"] == 5


class _InstantEngine:
    """Duck-typed ServingEngine: instant cold start and serve."""

    def __init__(self):
        self.cold_start_s = None
        self.registry = {}

    def cold_start(self, ctx=None):
        self.cold_start_s = 0.001
        return self.cold_start_s

    def serve(self, entry, tokens, **kw):
        return tokens, 0.0005


def test_engine_pool_cold_span_only_on_miss():
    from repro.serving.engine import EnginePool
    configure_tracing(enabled=True)
    pool = EnginePool({"m": _InstantEngine}, max_warm=2)
    pool.dispatch("m", "generate", [1])     # miss -> cold
    pool.dispatch("m", "generate", [1])     # hit -> warm
    spans = get_tracer().snapshot()
    dispatches = [s for s in spans if s.name == "engine_dispatch"]
    colds = [s for s in spans if s.name == "cold_start"]
    assert [d.attrs["path"] for d in dispatches] == ["cold", "warm"]
    assert len(colds) == 1
    assert colds[0].parent_id == dispatches[0].span_id
    snap = default_registry().snapshot()
    fam = {f["name"]: f for f in snap["families"]}
    ent = fam["repro_engine_dispatch_total"]
    series = {tuple(s["labels"]): s["value"] for s in ent["series"]}
    assert ent["labels"] == ["model", "path"]
    assert series[("m", "cold")] == 1
    assert series[("m", "warm")] == 1


def test_engine_pool_stats_breaks_out_pool_saturated_sheds():
    from repro.serving.engine import EnginePool, PoolSaturated

    class _SlowColdEngine(_InstantEngine):
        def cold_start(self, ctx=None):
            time.sleep(0.2)
            self.cold_start_s = 0.2
            return self.cold_start_s

    pool = EnginePool({"m": _SlowColdEngine}, max_warm=1,
                      queue_depth=0)
    sheds = []
    t = threading.Thread(target=lambda: pool.dispatch(
        "m", "generate", [1]))
    t.start()
    time.sleep(0.05)  # builder is mid-cold-start; depth 0 -> shed
    with pytest.raises(PoolSaturated):
        pool.dispatch("m", "generate", [1])
    t.join()
    stats = pool.stats()
    assert stats["sheds"] == 1
    assert stats["shed_reasons"] == {"pool-saturated": 1}


def test_tracer_disabled_daemon_path_untouched():
    """The whole serve path with tracing off records nothing."""
    from repro.pool.trace import Request
    d = _sim_daemon()
    d.start("t")
    for i in range(3):
        d.submit(Request(t=float(i), app="a"))
    d.shutdown(end_t=5.0)
    assert get_tracer().snapshot() == []


# ---------------------------------------------------------------------------
# slow tier: child-process spans over the fork-server protocol
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def suite_root():
    from repro.benchsuite.genlibs import build_suite
    return build_suite()


@pytest.mark.slow
def test_forkserver_spans_survive_protocol_roundtrip(suite_root):
    from repro.pool.forkserver import ForkServer
    configure_tracing(enabled=True)
    tracer = get_tracer()
    app_dir = os.path.join(suite_root, "apps", "graph_bfs")
    with ForkServer(app_dir, preload=["fakelib_igraph"]) as fs:
        with tracer.span("request", app="graph_bfs") as root:
            m = fs.exec(invocations=1, seed=1, trace=root.ctx())
        spans = m.get("spans", [])
        assert spans, "traced exec must ship child spans back"
        names = [s["name"] for s in spans]
        assert "fork" in names and "invoke" in names
        assert any(n.startswith("import:") for n in names)
        # every child span joins the caller's trace, rooted under it
        ids = {s["span_id"] for s in spans}
        for s in spans:
            assert s["trace_id"] == root.trace_id
            assert s.get("parent_id") in ids | {root.span_id}
        # the child clock (CLOCK_MONOTONIC) is system-wide: spans nest
        # inside the parent-side request wall time
        tracer.record_dicts(spans)
        all_spans = {s.span_id: s for s in tracer.snapshot()}
        req = all_spans[root.span_id]
        fork = next(s for s in tracer.snapshot() if s.name == "fork")
        assert fork.t_start_ms >= req.t_start_ms - 1.0
        assert (fork.t_start_ms + fork.duration_ms
                <= req.t_start_ms + req.duration_ms + 1.0)
        # an untraced exec ships no spans
        m2 = fs.exec(invocations=1, seed=2)
        assert "spans" not in m2


@pytest.mark.slow
def test_fleet_cold_requests_carry_fork_spans_warm_dont(suite_root):
    """Pool-path (zygote) requests fork and import; cold-path requests
    go through the subprocess cold_start span instead."""
    from repro.pool.fleet import ZygoteFleet
    from repro.pool.trace import Request, Trace
    configure_tracing(enabled=True)
    apps = {"echo": os.path.join(suite_root, "apps", "echo")}
    with ZygoteFleet(apps, budget_mb=4096.0) as fleet:
        fleet.replay(Trace(name="t",
                           requests=[Request(0.0, "echo"),
                                     Request(1.0, "echo")],
                           duration_s=2.0))
    by_trace = {}
    for s in get_tracer().snapshot():
        by_trace.setdefault(s.trace_id, []).append(s)
    req_traces = [ss for ss in by_trace.values()
                  if any(s.name == "request" for s in ss)]
    assert len(req_traces) == 2
    for ss in req_traces:
        names = {s.name for s in ss}
        root = next(s for s in ss if s.name == "request")
        assert root.attrs["path"] == "pool"
        # zygote dispatch = fork + handler import, never cold_start
        assert "fork" in names and "cold_start" not in names
        assert any(n.startswith("import") for n in names)
    # boot traces exist and are separate from request traces
    boots = [ss for ss in by_trace.values()
             if any(s.name == "zygote_boot" for s in ss)]
    assert len(boots) == 1
