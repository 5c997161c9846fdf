"""Serving driver: SLIMSTART-instrumented serverless model server.

Simulates the paper's full CI/CD loop on a model at its published
widths (``--reduced`` for the small same-family CPU rehearsal config):
  1. cold start under a policy (eager | lazy | slimstart),
  2. serve a skewed multi-entry workload (the paper's Fig. 3 shape),
  3. emit the SLIMSTART report; --optimize re-derives the policy from
     the profile and re-measures the cold start (the Level-B analogue of
     the AST deferred-import rewrite).

    PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-1b-a400m \
        --requests 20 --policy slimstart

JAX's persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``
where that is set, and otherwise in ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.serving import LoadPolicy, ServingEngine

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, places it (JAX reads the
    variable itself).  Otherwise it goes to the fixed ``<checkout>/
    .jax_cache``: the directory is part of what a later run must find,
    so it never carries a temp name, pid or time."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def skewed_workload(entries, n, seed=0, alpha=0.85):
    """Zipf-skewed entry mix: the top handler dominates (Obs. 3)."""
    rng = np.random.default_rng(seed)
    p = np.array([alpha ** i for i in range(len(entries))], np.float64)
    p /= p.sum()
    # make the skew strong: square and renormalize
    p = p ** 3
    p /= p.sum()
    return [entries[i] for i in rng.choice(len(entries), size=n, p=p)]


def run_service(cfg, policy, requests, *, seed=0, max_new=4):
    eng = ServingEngine(cfg, policy=policy, batch_size=1, prefill_len=8,
                        max_len=32)
    cold = eng.cold_start()
    rng = np.random.default_rng(seed)
    lat = {}
    for entry in requests:
        toks = rng.integers(0, cfg.vocab, (1, 8))
        _, dt = eng.serve(entry, toks, max_new_tokens=max_new)
        lat.setdefault(entry, []).append(dt)
    return eng, cold, lat


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-1b-a400m")
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--policy", default="slimstart",
                    choices=["eager", "lazy", "slimstart"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the small same-family config (CPU "
                         "rehearsal) instead of the published widths")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    probe = ServingEngine(cfg, batch_size=1)
    entries = probe.entries()
    workload = skewed_workload(entries, args.requests, seed=args.seed)

    if args.policy == "eager":
        policy = LoadPolicy.eager_all()
    elif args.policy == "lazy":
        policy = LoadPolicy(lazy_groups=frozenset(
            {"compile", "frontend", "experts"}))
    else:
        # profile-guided: run an eager profiling pass first, then build
        # the policy from the report (the paper's CI/CD loop)
        prof_eng, _, _ = run_service(cfg, LoadPolicy.eager_all(),
                                     workload, seed=args.seed)
        policy = LoadPolicy.from_report(prof_eng.report())

    eng, cold, lat = run_service(cfg, policy, workload, seed=args.seed)
    rep = eng.report()
    out = {
        "arch": cfg.name,
        "policy": args.policy,
        "cold_start_s": round(cold, 4),
        "entry_latency_mean_s": {
            k: round(float(np.mean(v)), 4) for k, v in lat.items()},
        "total_init_s": rep["total_init_s"],
        "by_group": rep["by_group"],
        "entry_counts": rep["entry_counts"],
    }
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
