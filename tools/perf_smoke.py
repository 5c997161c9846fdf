#!/usr/bin/env python3
"""CI perf smoke gate for the shared-base two-tier fleet.

Replays one small deterministic Azure-style trace through the simulated
fleet twice — one-zygote-per-app (PR 2 shape) and ``--shared-base``
(PR 5 two-tier) — via the real ``python -m repro fleet replay`` CLI,
then fails (exit 1) if shared-base *regresses* cold-start ratio or
memory GB-s beyond the checked-in tolerances in
``tools/perf_tolerance.json``.  The simulation is deterministic, so a
failure is a code regression, not noise.

Synthetic per-app report artifacts (one hot lib shared fleet-wide, one
private) are generated into a temp reports-dir so the profile-guided
policy actually admits zygotes — without reports the sweep would run
zygote-less and the gate would compare nothing.

Usage::

    python tools/perf_smoke.py [--keep out-dir] [--tolerance FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))

APPS = ["alpha", "beta", "gamma"]
# budget sized so BOTH fleets reach the same (zero) cold-start ratio:
# the memory check then compares GB-s at equal service quality, the
# tentpole's claim.  (Tighter budgets make shared-base trade memory for
# a much lower cold ratio, which a scalar memory gate would misread as
# a regression.)
REPLAY_ARGS = ["--minutes", "8", "--peak-rpm", "40", "--seed", "7",
               "--budget-mb", "420", "--policy", "profile",
               "--zygote-rss-mb", "96", "--shared-base-mb", "64"]


def _write_reports(reports_dir: str) -> None:
    from repro.api import save_report
    from repro.core.profiler.report import OptimizationReport
    from repro.core.profiler.utilization import LibraryStats

    def stat(name: str) -> LibraryStats:
        return LibraryStats(name=name, utilization=0.9, init_s=0.12,
                            init_share=0.5, runtime_samples=60,
                            file="<perf-smoke>")

    for app in APPS:
        rep = OptimizationReport(
            application=app, e2e_s=0.25, total_init_s=0.2,
            qualifies=True,
            stats=[stat("fakelib_shared"), stat(f"fakelib_{app}")],
            defer_targets=[])
        save_report(rep, os.path.join(reports_dir, f"{app}.json"))


def _replay(out_path: str, reports_dir: str, *extra: str) -> None:
    cmd = [sys.executable, "-m", "repro", "fleet", "replay",
           "--apps", ",".join(APPS), "--reports-dir", reports_dir,
           "--out", out_path, *REPLAY_ARGS, *extra]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=300)
    if proc.returncode != 0:
        raise RuntimeError(
            f"fleet replay failed ({proc.returncode}):\n"
            f"{proc.stderr[-2000:]}")


def _tracer_overhead(n: int = 2000, runs: int = 3):
    """Wall time of an in-process sim replay, tracing off vs on.

    min-of-N runs each way so scheduler noise doesn't trip the gate;
    the simulation itself is deterministic.
    """
    import time

    from repro.obs.tracing import configure_tracing, get_tracer
    from repro.pool import (
        AppProfile, FleetDaemon, FleetManager, IdleTimeoutPolicy,
        QueueConfig, SimFleetBackend,
    )
    from repro.pool.trace import Request

    def one() -> float:
        profiles = {a: AppProfile(app=a, cold_init_ms=400.0,
                                  warm_init_ms=20.0, invoke_ms=30.0,
                                  rss_mb=100.0) for a in APPS}
        manager = FleetManager(
            profiles, IdleTimeoutPolicy(timeout_s=60.0),
            budget_mb=2048.0,
            queue=QueueConfig(depth=64, max_concurrency=4))
        daemon = FleetDaemon(SimFleetBackend(manager))
        daemon.start("perf-smoke")
        t0 = time.perf_counter()
        for i in range(n):
            daemon.submit(Request(t=i * 0.01, app=APPS[i % len(APPS)]))
        dt = time.perf_counter() - t0
        daemon.shutdown(end_t=n * 0.01 + 120.0)
        get_tracer().clear()
        return dt

    configure_tracing(enabled=False)
    off_s = min(one() for _ in range(runs))
    configure_tracing(enabled=True)
    on_s = min(one() for _ in range(runs))
    configure_tracing(enabled=False)
    return off_s, on_s


def _adaptive_overhead(n: int = 4000, runs: int = 3):
    """Wall time of an in-process sim replay, adaptive loop off vs on.

    The closed loop promises the serving path pays only the per-arrival
    drift-detector bookkeeping (the child-side sampler rides sampled
    *forked* execs, which the sim doesn't fork); this holds the
    end-to-end submit loop to the <=3 % p50 budget, min-of-N runs.
    Window size is chosen so several windows actually close (and score)
    inside the run — the gate covers the window-close path too.
    """
    import time

    from repro.core.adaptive import AdaptiveConfig, DriftConfig
    from repro.pool import (
        AppProfile, FleetDaemon, FleetManager, IdleTimeoutPolicy,
        QueueConfig, SimFleetBackend,
    )
    from repro.pool.daemon import make_sim_adaptive_loop
    from repro.pool.trace import Request

    def one(adaptive: bool) -> float:
        profiles = {a: AppProfile(app=a, cold_init_ms=400.0,
                                  warm_init_ms=20.0, invoke_ms=30.0,
                                  rss_mb=100.0) for a in APPS}
        manager = FleetManager(
            profiles, IdleTimeoutPolicy(timeout_s=60.0),
            budget_mb=2048.0,
            queue=QueueConfig(depth=64, max_concurrency=4))
        loop = None
        if adaptive:
            loop = make_sim_adaptive_loop(
                manager, config=AdaptiveConfig(
                    drift=DriftConfig(window_s=5.0)))
        daemon = FleetDaemon(SimFleetBackend(manager, adaptive=loop))
        daemon.start("perf-smoke-adaptive")
        t0 = time.perf_counter()
        for i in range(n):
            daemon.submit(Request(t=i * 0.01, app=APPS[i % len(APPS)]))
        dt = time.perf_counter() - t0
        daemon.shutdown(end_t=n * 0.01 + 120.0)
        return dt

    off_s = min(one(False) for _ in range(runs))
    on_s = min(one(True) for _ in range(runs))
    return off_s, on_s


def _fault_hook_overhead(n: int = 4000, runs: int = 3):
    """Dispatch wall time with the chaos ``fault_hook`` unset vs a
    no-op hook installed.

    The serving path promises that a disabled hook costs one
    ``is not None`` check; this measures an EnginePool dispatch loop
    (every request a cold start, the hook's hottest placement) both
    ways, min-of-N runs.  Fake duck-typed engines keep the loop pure
    dispatch machinery — no real model builds.
    """
    import time

    from repro.serving.engine import EnginePool

    class _FakeEngine:
        cold_start_s = 0.0   # read by the eviction amortizer
        registry = {}        # no components to drop on eviction

        def cold_start(self, ctx=None):
            return 0.0

        def serve(self, entry, tokens, **kw):
            return None, 0.0

    models = ["m0", "m1"]

    def one(hook) -> float:
        # max_warm=1 with two alternating models: every dispatch
        # evicts + cold-starts, so the hook site runs per request
        pool = EnginePool({m: _FakeEngine for m in models},
                          max_warm=1, fault_hook=hook)
        t0 = time.perf_counter()
        for i in range(n):
            pool.dispatch(models[i % 2], "generate", None)
        return time.perf_counter() - t0

    off_s = min(one(None) for _ in range(runs))
    on_s = min(one(lambda site, **ctx: None) for _ in range(runs))
    return off_s, on_s


def _ha_overhead(n: int = 1500, runs: int = 3):
    """Routing-path cost of the HA machinery (ISSUE 10).

    Same socket-fed router + one sim node agent both ways; the "on"
    arm additionally enables ledger replication with ZERO standbys
    attached — the promised idle cost is one ``is not None`` check
    plus an entry publish into an empty connection list per route.
    Every call already runs under :class:`RetryPolicy` (that IS the
    plain path now); this bounds what replication adds on top,
    min-of-N runs over a socket round-trip baseline.
    """
    import time

    from repro.cluster import (ClusterRouter, NodeAgent, NodeClient,
                               RetryPolicy)
    from repro.pool import (
        AppProfile, FleetManager, IdleTimeoutPolicy, QueueConfig,
        SimFleetBackend,
    )

    def one(replicate: bool) -> float:
        profiles = {a: AppProfile(app=a, cold_init_ms=400.0,
                                  warm_init_ms=20.0, invoke_ms=30.0,
                                  rss_mb=100.0) for a in APPS}
        manager = FleetManager(
            profiles, IdleTimeoutPolicy(timeout_s=60.0),
            budget_mb=2048.0,
            queue=QueueConfig(depth=64, max_concurrency=4))
        agent = NodeAgent(SimFleetBackend(manager), node_id="perf",
                          port=0)
        agent.start()
        try:
            router = ClusterRouter(
                {"perf": NodeClient("perf", agent.host, agent.port,
                                    retry=RetryPolicy(seed=7))},
                strategy="hash", seed=7, retry=RetryPolicy(seed=7))
            router.connect()
            if replicate:
                router.enable_replication()
            t0 = time.perf_counter()
            for i in range(n):
                router.route(APPS[i % len(APPS)])
            dt = time.perf_counter() - t0
            router.shutdown()
        finally:
            agent.result()
        return dt

    off_s = min(one(False) for _ in range(runs))
    on_s = min(one(True) for _ in range(runs))
    return off_s, on_s


def _cluster_check(tol: dict, check) -> None:
    """In-process cluster placement gate: sharing vs hash at equal
    budgets on a deterministic Zipf workload, plus conservation and a
    wall-clock bound on the replay (the cluster simulator's
    scale-out promise)."""
    import time

    from repro.cluster import compare_strategies, synthetic_cluster_workload

    wl = synthetic_cluster_workload(16, n_families=4, seed=7,
                                    minutes=10, peak_rpm=80.0)
    t0 = time.perf_counter()
    results = compare_strategies(wl, n_nodes=4, node_budget_mb=512.0,
                                 strategies=("sharing", "hash"), seed=7)
    replay_s = time.perf_counter() - t0
    sharing, hashed = results["sharing"], results["hash"]
    dr = sharing["cold_start_ratio"] - hashed["cold_start_ratio"]
    check("cluster placement",
          dr <= tol["max_cold_ratio_vs_hash"],
          f"sharing {sharing['cold_start_ratio']:.4f} vs hash "
          f"{hashed['cold_start_ratio']:.4f} cold ratio "
          f"(delta {dr:+.4f}, allowed "
          f"+{tol['max_cold_ratio_vs_hash']})")
    check("cluster conservation",
          all(p["conservation"]["holds"] for p in results.values()),
          f"sharing={sharing['conservation']['holds']} "
          f"hash={hashed['conservation']['holds']}")
    n_req = sharing["requests"] + hashed["requests"]
    check("cluster replay throughput",
          n_req >= tol["min_replay_requests"]
          and replay_s <= tol["max_replay_s"],
          f"{n_req} arrivals through 2 x 4 simulated nodes in "
          f"{replay_s:.2f} s (need >= {tol['min_replay_requests']} "
          f"within {tol['max_replay_s']} s)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tolerance",
                    default=os.path.join(REPO, "tools",
                                         "perf_tolerance.json"))
    ap.add_argument("--keep", default=None,
                    help="directory to keep the two fleet_summary "
                         "artifacts in (default: temp)")
    args = ap.parse_args(argv)

    with open(args.tolerance) as fh:
        all_tol = json.load(fh)
    tol = all_tol["shared_base"]

    from repro.api import load_fleet_summary

    out_dir = args.keep or tempfile.mkdtemp(prefix="perf-smoke-")
    os.makedirs(out_dir, exist_ok=True)
    reports_dir = os.path.join(out_dir, "reports")
    os.makedirs(reports_dir, exist_ok=True)
    _write_reports(reports_dir)

    base_path = os.path.join(out_dir, "one-per-app.json")
    shared_path = os.path.join(out_dir, "shared-base.json")
    _replay(base_path, reports_dir)
    _replay(shared_path, reports_dir, "--shared-base")

    base = load_fleet_summary(base_path)
    shared = load_fleet_summary(shared_path)

    checks = []

    def check(name: str, ok: bool, detail: str) -> None:
        checks.append(ok)
        print(f"  [{'ok' if ok else 'FAIL'}] {name}: {detail}")

    print(f"perf smoke: {base['requests']} requests, "
          f"budget {base.get('budget_mb')} MB")
    dr = shared["cold_start_ratio"] - base["cold_start_ratio"]
    check("cold-start ratio",
          dr <= tol["max_cold_ratio_regression"],
          f"one-per-app {base['cold_start_ratio']:.4f} vs shared-base "
          f"{shared['cold_start_ratio']:.4f} (delta {dr:+.4f}, "
          f"allowed +{tol['max_cold_ratio_regression']})")
    mem_b, mem_s = base["memory_gb_s"], shared["memory_gb_s"]
    limit = mem_b * (1.0 + tol["max_memory_regression_frac"])
    check("memory GB-s", mem_s <= limit,
          f"one-per-app {mem_b} vs shared-base {mem_s} "
          f"(limit {limit:.3f})")
    check("two-tier actually on",
          shared.get("shared_base_mb", 0) > 0
          and shared.get("pool_starts", 0) > 0,
          f"shared_base_mb={shared.get('shared_base_mb')} "
          f"pool_starts={shared.get('pool_starts')} (zygotes admitted "
          f"and serving forks)")

    ttol = all_tol["tracer"]
    n_req = 2000
    off_s, on_s = _tracer_overhead(n=n_req)
    frac = (on_s - off_s) / off_s if off_s else 0.0
    per_req_us = (on_s - off_s) / n_req * 1e6
    check("tracer overhead",
          frac <= ttol["max_overhead_frac"]
          or per_req_us <= ttol["max_per_request_us"],
          f"sim replay off {off_s * 1e3:.1f} ms vs on "
          f"{on_s * 1e3:.1f} ms ({frac * 100:+.1f}%, "
          f"{per_req_us:+.1f} us/req; allowed "
          f"{ttol['max_overhead_frac'] * 100:.0f}% or "
          f"{ttol['max_per_request_us']} us/req)")

    ftol = all_tol["fault_hook"]
    n_disp = 4000
    off_s, on_s = _fault_hook_overhead(n=n_disp)
    frac = (on_s - off_s) / off_s if off_s else 0.0
    per_req_us = (on_s - off_s) / n_disp * 1e6
    check("fault_hook overhead",
          frac <= ftol["max_overhead_frac"]
          or per_req_us <= ftol["max_per_request_us"],
          f"hook unset {off_s * 1e3:.1f} ms vs no-op hook "
          f"{on_s * 1e3:.1f} ms over {n_disp} dispatches "
          f"({frac * 100:+.1f}%, {per_req_us:+.2f} us/req; allowed "
          f"{ftol['max_overhead_frac'] * 100:.0f}% or "
          f"{ftol['max_per_request_us']} us/req)")

    atol = all_tol["adaptive"]
    n_sub = 4000
    off_s, on_s = _adaptive_overhead(n=n_sub)
    frac = (on_s - off_s) / off_s if off_s else 0.0
    per_req_us = (on_s - off_s) / n_sub * 1e6
    check("adaptive-loop overhead",
          frac <= atol["max_overhead_frac"]
          or per_req_us <= atol["max_per_request_us"],
          f"sim replay static {off_s * 1e3:.1f} ms vs adaptive "
          f"{on_s * 1e3:.1f} ms over {n_sub} submits "
          f"({frac * 100:+.1f}%, {per_req_us:+.2f} us/req; allowed "
          f"{atol['max_overhead_frac'] * 100:.0f}% or "
          f"{atol['max_per_request_us']} us/req)")

    htol = all_tol["cluster_ha"]
    n_route = 1500
    off_s, on_s = _ha_overhead(n=n_route)
    frac = (on_s - off_s) / off_s if off_s else 0.0
    per_req_us = (on_s - off_s) / n_route * 1e6
    check("ha routing overhead",
          frac <= htol["max_overhead_frac"]
          or per_req_us <= htol["max_per_request_us"],
          f"replication off {off_s * 1e3:.1f} ms vs on (zero "
          f"standbys) {on_s * 1e3:.1f} ms over {n_route} routes "
          f"({frac * 100:+.1f}%, {per_req_us:+.2f} us/req; allowed "
          f"{htol['max_overhead_frac'] * 100:.0f}% or "
          f"{htol['max_per_request_us']} us/req)")

    _cluster_check(all_tol["cluster"], check)

    if all(checks):
        print("perf smoke: PASS — shared-base does not regress the "
              "one-zygote-per-app fleet")
        return 0
    print("perf smoke: FAIL — shared-base regressed beyond "
          f"{args.tolerance}", file=sys.stderr)
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
