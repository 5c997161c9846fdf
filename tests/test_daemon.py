"""Fleet daemon lifecycle tests: bounded-queue backpressure, rewarm
ticks, graceful drain (including the SIGTERM flush path), and the
fleet_summary artifact both backends emit.

Fast tier: in-process sim daemon (simulated time, no subprocesses).
Slow tier: the real threaded loop over a ZygoteFleet, and
``python -m repro fleet serve --sim --stdin`` killed with SIGTERM.
"""

import io
import json
import os
import signal
import subprocess
import sys
import time

import pytest

from repro.api import load_fleet_summary, save_report
from repro.core.profiler.report import OptimizationReport
from repro.core.profiler.utilization import LibraryStats
from repro.pool import (
    AppProfile,
    FleetDaemon,
    FleetManager,
    IdleTimeoutPolicy,
    ProfileGuidedPolicy,
    QueueConfig,
    Request,
    SimFleetBackend,
    Trace,
)

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def _report(app: str, lib: str = "fakelib_hot") -> OptimizationReport:
    stat = LibraryStats(name=lib, utilization=0.9, init_s=0.15,
                        init_share=0.5, runtime_samples=90, file="<x>")
    return OptimizationReport(application=app, e2e_s=0.3,
                              total_init_s=0.15, qualifies=True,
                              stats=[stat], defer_targets=[])


def _profiles(*apps, invoke_ms=500.0, cold_ms=500.0):
    return {a: AppProfile(app=a, cold_init_ms=cold_ms, warm_init_ms=20.0,
                          invoke_ms=invoke_ms, rss_mb=100.0)
            for a in apps}


def _sim_daemon(queue, *, apps=("a",), policy=None, reports_dir=None,
                summary_path=None, **daemon_kw) -> FleetDaemon:
    manager = FleetManager(_profiles(*apps),
                           policy or IdleTimeoutPolicy(timeout_s=60.0),
                           budget_mb=2048.0, queue=queue)
    backend = SimFleetBackend(manager, reports_dir=reports_dir)
    return FleetDaemon(backend, summary_path=summary_path, **daemon_kw)


def _burst(n, app="a", gap_s=0.05, duration_s=60.0) -> Trace:
    return Trace("burst", [Request(gap_s * i, app) for i in range(n)],
                 duration_s)


# ---------------------------------------------------------------------------
# fast tier: sim backend
# ---------------------------------------------------------------------------

def test_sim_daemon_conservation_and_summary_artifact(tmp_path):
    out = str(tmp_path / "summary.json")
    d = _sim_daemon(QueueConfig(depth=3, max_concurrency=1),
                    summary_path=out)
    d.start("burst")
    payload = d.run_trace(_burst(20))
    # arrival conservation: every request is served, shed or flushed
    assert payload["requests"] == 20
    assert payload["requests"] == (payload["served"] + payload["sheds"]
                                   + payload["flushed"])
    assert payload["sheds"] > 0  # 20 req/s against ~2/s of capacity
    assert payload["queue_wait_p99_ms"] > 0
    # queue waits surface in end-to-end latency, not beside it
    assert payload["p99_ms"] >= payload["queue_wait_p99_ms"]
    loaded = load_fleet_summary(out)
    assert loaded["source"] == "serve-sim"
    assert loaded["requests"] == 20
    assert loaded["queue"] == {"depth": 3, "max_concurrency": 1,
                               "shed_policy": "reject-new"}
    # the admission breakdown lands in the *saved* artifact too, not
    # just the in-memory payload
    assert sum(loaded["meta"]["admission"].values()) == 20


def test_sim_daemon_drop_oldest_sheds_waiting_not_arriving():
    d = _sim_daemon(QueueConfig(depth=3, max_concurrency=1,
                                shed_policy="drop-oldest"))
    d.start("burst")
    payload = d.run_trace(_burst(20))
    assert payload["sheds"] > 0
    assert payload["requests"] == (payload["served"] + payload["sheds"]
                                   + payload["flushed"])
    # drop-oldest sheds the *waiting* request — the breakdown names it
    assert payload["shed_reasons"] == {"drop-oldest": payload["sheds"]}


def test_sim_daemon_shed_reason_breakdown_sums_to_sheds(tmp_path):
    out = str(tmp_path / "summary.json")
    d = _sim_daemon(QueueConfig(depth=3, max_concurrency=1),
                    summary_path=out)
    d.start("burst")
    payload = d.run_trace(_burst(20))
    assert payload["sheds"] > 0
    assert sum(payload["shed_reasons"].values()) == payload["sheds"]
    # reject-new policy: every shed is a queue-full rejection
    assert set(payload["shed_reasons"]) == {"queue-full"}
    # per-app rows carry the same breakdown, and it also sums
    per_app = {row["app"]: row for row in payload["per_app"]}
    assert sum(sum(r.get("shed_reasons", {}).values())
               for r in per_app.values()) == payload["sheds"]
    # the breakdown survives the artifact round-trip (optional key)
    loaded = load_fleet_summary(out)
    assert loaded["shed_reasons"] == payload["shed_reasons"]


def test_real_backend_shed_reasons_and_locked_snapshot():
    """Admission bookkeeping of the real backend without booting
    zygotes: shed causes are named, and snapshot() aggregates from a
    copy taken under the queue lock."""
    from collections import deque

    from repro.pool.daemon import RealFleetBackend, _AppServeStats

    class _StubFleet:
        app_dirs = {"a": "."}
        shared_base = False

    def _backend(policy):
        be = RealFleetBackend(
            _StubFleet(),
            queue=QueueConfig(depth=1, max_concurrency=1,
                              shed_policy=policy))
        # start() would boot zygotes; wire the admission state directly
        be._queues["a"] = deque()
        be._stats["a"] = _AppServeStats()
        be._in_flight["a"] = 0
        return be

    be = _backend("reject-new")
    assert be.submit(Request(0.0, "a")) == "queued"
    assert be.submit(Request(0.1, "a")) == "shed"
    snap = be.snapshot()
    assert snap["requests"] == 2 and snap["sheds"] == 1
    assert snap["shed_reasons"] == {"queue-full": 1}
    assert snap["per_app"]["a"]["queued"] == 1
    # the snapshot is a copy: mutating it must not corrupt live stats
    snap["shed_reasons"]["queue-full"] = 99
    assert be._stats["a"].shed_reasons == {"queue-full": 1}

    be = _backend("drop-oldest")
    assert be.submit(Request(0.0, "a")) == "queued"
    assert be.submit(Request(0.1, "a")) == "queued"  # displaces oldest
    st = be._stats["a"]
    assert st.arrivals == 2 and st.sheds == 1
    assert st.shed_reasons == {"drop-oldest": 1}
    assert len(be._queues["a"]) == 1


def test_sim_daemon_unbounded_without_queue_config():
    manager = FleetManager(_profiles("a"),
                           IdleTimeoutPolicy(timeout_s=60.0),
                           budget_mb=2048.0)  # queue=None
    d = FleetDaemon(SimFleetBackend(manager))
    d.start("burst")
    payload = d.run_trace(_burst(20))
    assert payload["sheds"] == 0 and payload["served"] == 20
    assert payload["queue"] is None


def test_sim_daemon_flushes_queued_on_early_end():
    """Requests still queued at the horizon (nothing freed in time)
    are flushed, never silently dropped."""
    d = _sim_daemon(QueueConfig(depth=8, max_concurrency=1))
    d.start("tail")
    # all 5 arrive in the last 100 ms of a 1 s horizon; service takes
    # 520 ms, so at most 2 can even start by the end
    trace = Trace("tail", [Request(0.9 + 0.01 * i, "a")
                           for i in range(5)], 1.0)
    payload = d.run_trace(trace)
    assert payload["flushed"] > 0
    assert payload["requests"] == (payload["served"] + payload["sheds"]
                                   + payload["flushed"])


def test_rewarm_tick_loads_report_and_keeps_serving(tmp_path):
    """A rewarm tick mid-stream re-loads the deployed report artifact
    into the policy (defer-set drift reaches the fleet) and drops no
    in-flight or queued work."""
    reports_dir = str(tmp_path)
    policy = ProfileGuidedPolicy(rate_hint_per_s=1.0)
    d = _sim_daemon(QueueConfig(depth=8, max_concurrency=2),
                    policy=policy, reports_dir=reports_dir)
    d.start("live")
    assert policy.preload_modules("a") == []  # no report deployed yet
    for i in range(5):
        d.submit(Request(0.1 * i, "a"))
    # "external CI run" deploys a fresh report artifact, timer fires
    save_report(_report("a"), os.path.join(reports_dir, "a.json"))
    tick = d.rewarm_now()
    assert tick == {"a": {"ok": True}}
    assert d.rewarm_ticks == 1
    assert policy.preload_modules("a")  # hot set arrived
    for i in range(5, 10):
        d.submit(Request(0.1 * i, "a"))
    payload = d.shutdown(end_t=60.0)
    assert payload["rewarm_ticks"] == 1
    assert payload["served"] == 10  # the tick dropped nothing
    assert payload["flushed"] == 0 and payload["sheds"] == 0


def test_rewarm_timer_thread_fires():
    d = _sim_daemon(QueueConfig(depth=4), rewarm_interval_s=0.05)
    d.start("live")
    time.sleep(0.3)
    payload = d.shutdown(end_t=1.0)
    assert payload["rewarm_ticks"] >= 2
    assert d.rewarm_errors == []


def test_rewarm_failure_is_recorded_not_raised():
    def boom():
        raise RuntimeError("artifact store down")
    manager = FleetManager(_profiles("a"), IdleTimeoutPolicy(),
                           budget_mb=1024.0, queue=QueueConfig())
    d = FleetDaemon(SimFleetBackend(manager), rewarm_fn=boom)
    d.start("live")
    out = d.rewarm_now()
    assert out["ok"] is False
    assert d.rewarm_ticks == 0 and len(d.rewarm_errors) == 1
    d.submit(Request(0.0, "a"))
    assert d.shutdown(end_t=1.0)["served"] == 1


def test_stdin_loop_protocol_and_eof_drain():
    d = _sim_daemon(QueueConfig(depth=8, max_concurrency=4))
    d.start("live")
    feed = io.StringIO("\n".join([
        json.dumps({"app": "a"}),
        json.dumps({"app": "a"}),
        "not json",
        json.dumps({"cmd": "stats"}),
        json.dumps({"cmd": "nope"}),
        json.dumps({"app": "unknown-app"}),
        json.dumps({"handler": "x"}),  # no app, no cmd
    ]) + "\n")
    out = io.StringIO()
    clock_t = iter([0.0] + [0.1 * i for i in range(1, 100)])
    payload = d.run_stdin(feed, out, clock=lambda: next(clock_t))
    replies = [json.loads(line) for line in
               out.getvalue().strip().splitlines()]
    assert replies[0]["outcome"] in ("served", "queued")
    assert replies[2] == {"ok": False, "error": "bad json"}
    assert replies[3]["ok"] and "stats" in replies[3]
    assert not replies[4]["ok"]  # unknown cmd
    assert not replies[5]["ok"] and "unknown app" in replies[5]["error"]
    assert not replies[6]["ok"]
    assert replies[-1]["event"] == "summary"
    assert payload["requests"] == 2 and payload["served"] == 2


def test_shutdown_is_idempotent():
    d = _sim_daemon(QueueConfig(depth=4))
    d.start("live")
    d.submit(Request(0.0, "a"))
    p1 = d.shutdown(end_t=10.0)
    p2 = d.shutdown(end_t=99.0)
    assert p1 is p2
    assert d.submit(Request(1.0, "a")) == "draining"


def test_serve_stage_emits_fleet_summary(tmp_path):
    from repro.api import ServeStage
    from repro.api.stages import RunContext
    from repro.pool.trace import poisson_trace
    ctx = RunContext(app="stage_app", root=str(tmp_path))
    stage = ServeStage(sim=True,
                       trace=poisson_trace("stage_app", rate_per_s=3.0,
                                           duration_s=20.0, seed=7),
                       queue_depth=8)
    stage.run(ctx)
    res = ctx.results["serve"]
    assert res["source"] == "serve-sim"
    assert res["requests"] > 0
    path = res["artifact_path"]
    assert load_fleet_summary(path)["requests"] == res["requests"]


# ---------------------------------------------------------------------------
# fast tier: EnginePool queue-aware dispatch (stub engines, real threads)
# ---------------------------------------------------------------------------

class _StubEngine:
    """Duck-typed ServingEngine: slow cold start, instant serve."""

    def __init__(self, cold_s: float = 0.2):
        self._cold_s = cold_s
        self.cold_start_s = None
        self.registry = {}

    def cold_start(self, ctx=None):
        time.sleep(self._cold_s)
        self.cold_start_s = self._cold_s
        return self._cold_s

    def serve(self, entry, tokens, **kw):
        return "out", 0.001


def test_engine_pool_single_flight_and_shed():
    import threading

    from repro.serving.engine import EnginePool, PoolSaturated

    builds = []

    def builder():
        builds.append(1)
        return _StubEngine()

    pool = EnginePool({"m": builder}, max_warm=1, queue_depth=2)
    paths, sheds = [], []

    def call():
        try:
            paths.append(pool.dispatch("m", "generate", None)[2])
        except PoolSaturated:
            sheds.append(1)

    threads = [threading.Thread(target=call) for _ in range(5)]
    for t in threads:
        t.start()
        time.sleep(0.02)  # deterministic arrival order
    for t in threads:
        t.join()
    # one build (single-flight), two waiters coalesced, two shed
    assert len(builds) == 1
    assert paths.count("cold") == 1 and paths.count("queued") == 2
    assert len(sheds) == 2
    stats = pool.stats()
    assert stats["sheds"] == 2 and stats["coalesced"] == 2
    assert stats["queue_wait_p99_s"] > 0
    # pool is warm now: no more waiting
    assert pool.dispatch("m", "generate", None)[2] == "warm"


def test_engine_pool_legacy_path_unchanged():
    from repro.serving.engine import EnginePool
    pool = EnginePool({"m": _StubEngine}, max_warm=1)  # queue_depth=None
    assert pool.dispatch("m", "generate", None)[2] == "cold"
    assert pool.dispatch("m", "generate", None)[2] == "warm"
    assert "sheds" in pool.stats() and pool.stats()["sheds"] == 0


# ---------------------------------------------------------------------------
# slow tier: real zygote fleet + subprocess SIGTERM
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def suite_root():
    from repro.benchsuite.genlibs import build_suite
    return build_suite()


@pytest.mark.slow
def test_real_daemon_serves_and_rewarms(suite_root, tmp_path):
    from repro.pool import RealFleetBackend, ZygoteFleet
    reports_dir = str(tmp_path)
    # hot set must name a library the deployed app really vendors — the
    # zygote imports it on the rewarm tick
    save_report(_report("graph_bfs", lib="fakelib_igraph"),
                os.path.join(reports_dir, "graph_bfs.json"))
    apps = {name: os.path.join(suite_root, "apps", name)
            for name in ["graph_bfs", "echo"]}
    fleet = ZygoteFleet(apps, budget_mb=4096.0)
    backend = RealFleetBackend(
        fleet, queue=QueueConfig(depth=8, max_concurrency=1),
        reports_dir=reports_dir)
    d = FleetDaemon(backend, summary_path=str(tmp_path / "sum.json"),
                    drain_timeout_s=120.0)
    d.start("real-live")
    for i in range(4):
        assert d.submit(Request(float(i), "graph_bfs",
                                handler="bfs")) == "queued"
    assert d.submit(Request(4.0, "echo")) == "queued"
    tick = d.rewarm_now()  # re-preloads graph_bfs's zygote mid-serve
    assert tick["graph_bfs"]["skipped"] is False
    payload = d.shutdown(flush=False)  # end-of-feed: serve the queue
    assert payload["served"] == 5 and payload["flushed"] == 0
    assert payload["pool_starts"] == 5  # all via resident zygotes
    assert payload["rewarm_ticks"] == 1
    assert payload["queue_wait_p99_ms"] > 0
    loaded = load_fleet_summary(str(tmp_path / "sum.json"))
    assert loaded["source"] == "serve-real"
    assert loaded["zygotes"] == ["echo", "graph_bfs"]


@pytest.mark.slow
def test_real_daemon_sigterm_flushes_queue(suite_root, tmp_path):
    """SIGTERM semantics end-to-end: in-flight finishes, queued work is
    flushed into the summary artifact, exit code 0."""
    out = str(tmp_path / "summary.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro", "fleet", "serve", "--sim",
         "--stdin", "--apps", "a,b", "--queue-depth", "32",
         "--summary-out", out, "--rewarm-interval-s", "0.2"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, env=env)
    try:
        for _ in range(6):
            proc.stdin.write(json.dumps({"app": "a"}) + "\n")
        proc.stdin.flush()
        time.sleep(1.0)
        proc.send_signal(signal.SIGTERM)
        stdout, _ = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0
    replies = [json.loads(line) for line in stdout.strip().splitlines()]
    assert replies[-1]["event"] == "summary"
    summary = load_fleet_summary(out)
    assert summary["requests"] == 6
    assert summary["requests"] == (summary["served"] + summary["sheds"]
                                   + summary["flushed"])
    assert summary["rewarm_ticks"] >= 1


@pytest.mark.slow
def test_fleet_replay_real_cli_emits_summary(suite_root, tmp_path):
    from repro.cli import main
    out = str(tmp_path / "replay.json")
    rc = main(["fleet", "replay", "--real", "--root", suite_root,
               "--apps", "graph_bfs,echo", "--minutes", "2",
               "--peak-rpm", "20", "--limit", "6", "--out", out])
    assert rc == 0
    summary = load_fleet_summary(out)
    assert summary["source"] == "replay-real"
    assert summary["requests"] == 6 and summary["served"] == 6
    assert summary["cold_starts"] + summary["pool_starts"] == 6


def test_engine_pool_eviction_defers_drop_during_inflight_serve():
    """Evicting a model while another thread is mid-serve on it must
    not drop its components under the request — the drop happens when
    the last in-flight serve returns."""
    import threading

    from repro.serving.engine import EnginePool

    class _Comp:
        def __init__(self):
            self.dropped = False

        def drop(self):
            self.dropped = True

    class _SlowServeEngine(_StubEngine):
        def __init__(self):
            super().__init__(cold_s=0.0)
            self.comp = _Comp()
            self.registry = {"c": self.comp}
            self.serving = threading.Event()
            self.release = threading.Event()

        def serve(self, entry, tokens, **kw):
            self.serving.set()
            assert self.release.wait(timeout=10)
            assert not self.comp.dropped  # must survive the eviction
            return "out", 0.001

    x_engine = _SlowServeEngine()
    pool = EnginePool({"x": lambda: x_engine, "y": _StubEngine},
                      max_warm=1, queue_depth=4)
    x_engine.release.set()                # let the cold serve through
    pool.dispatch("x", "generate", None)  # cold-start x
    x_engine.release.clear()
    x_engine.serving.clear()

    t = threading.Thread(
        target=lambda: pool.dispatch("x", "generate", None))
    t.start()
    assert x_engine.serving.wait(timeout=10)  # x is mid-serve
    pool.dispatch("y", "generate", None)      # evicts x (max_warm=1)
    assert "x" in pool.evictions
    assert not x_engine.comp.dropped          # drop deferred
    x_engine.release.set()
    t.join(timeout=10)
    assert x_engine.comp.dropped              # dropped on serve exit
