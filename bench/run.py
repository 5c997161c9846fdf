"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are read from
``BENCHMARK.json`` and the files it names.  The run:

1. pins JAX's persistent compilation cache to ``<checkout>/.jax_cache``
   and exits non-zero, printing no result, without a TPU of a kind in
   ``peaks.json`` or with fewer chips than the cell asks for;
2. sets up (``harness.set_up``): a profile pass on an eager engine, the
   ``EnginePool`` of the cell's tenants under the derived policy;
3. serves the cell's open-loop schedule for ``--seconds`` through
   ``EnginePool.dispatch`` (``--trace 1``: with the profiler on for the
   window's first seconds);
4. reads the device's peak memory, frees the pool and compares a
   sample of the served tokens with the float32 reference (``check``);
5. prints the cache hits and misses of set-up and window on one line,
   then the result as the last line of standard output; the numbers
   compared, each with its limit, are the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import Any, Optional  # noqa: E402

from pathlib import Path  # noqa: E402

# the program under test, from the checkout this file lies in
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import harness  # noqa: E402

TRACE_SECONDS = 8.0


@dataclass
class Run:
    """What a metric reader sees (``metrics/<name>.py``: ``read(run)``)."""
    spec: dict
    peaks: dict
    setup: dict
    window: Any
    peak_bytes: Optional[int]
    trace: Any = None


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def report(cell, run: Run, kind: str) -> dict:
    """The cell's metrics of ``kind``.  The cell lists each one as a
    cell where its reader finds something; one that reads nothing ends
    the run with no result."""
    out = {}
    for m in cell.metrics(kind):
        value = harness.metric_module(m["name"]).read(run)
        if value is None:
            raise SystemExit(f"bench: {m['name']} read nothing in "
                             f"{cell.name}, which lists it")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.load_cell(args.workload)
    peaks = harness.load_peaks()
    cache_dir = harness.pin_compile_cache()
    events = harness.CacheEvents()
    try:
        import repro.serving  # noqa: F401  (timed: the program's import)
    except ImportError as e:
        sys.exit(f"bench: the serving program does not import: {e}")
    dev = harness.chip_or_exit(cell.chips, peaks)
    import jax
    import_s = time.perf_counter() - T_START
    cfg = harness.program_config(cell.spec)
    served = harness.set_up(cell, cfg, args.seed)
    schedule = harness.traffic_module(cell.mix).schedule(
        cell.mix, harness.subseed(args.seed, 1), args.seconds, cfg.vocab)
    setup_cache = events.snapshot()
    if args.trace:
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    setup = {"setup_s": time.perf_counter() - T_START, "import_s": import_s,
             "first_coldstart_s": served.first_coldstart_s}

    window = harness.drive(
        served, schedule,
        trace_seconds=min(TRACE_SECONDS, args.seconds) if args.trace else 0)
    window_cache = {k: v - setup_cache[k]
                    for k, v in events.snapshot().items()}
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    harness.free(served)

    import check
    t_check = time.perf_counter()
    picked = check.sample(window.records, args.seed)
    reading = check.read(cell.spec, served.tenants, picked)
    check_s = time.perf_counter() - t_check
    failed = sum(r["path"] == "failed" for r in window.records)
    compared = check.verdict(cell.spec, reading, failed)

    run = Run(cell.spec, peaks[dev.device_kind], setup, window, peak)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": check.is_correct(compared, reading),
              "attempted": len(window.records), "failed": failed}
    if args.trace:
        import devtrace
        run.trace = devtrace.read(harness.TRACE_DIR)
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        result["metrics"] = report(cell, run, "per_layer")
        result["device"] = device
        result["breakdown"] = devtrace.breakdown(run.trace)
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    else:
        result["metrics"] = report(cell, run, "end_to_end")
        result["device"] = device
    result["compared"] = compared

    paths: dict[str, int] = {}
    for r in window.records:
        paths[r["path"]] = paths.get(r["path"], 0) + 1
    print(json.dumps({"compile_cache": {"dir": cache_dir,
                                        "set_up": setup_cache,
                                        "window": window_cache},
                      "paths": paths, "window_s": window.seconds,
                      "checked": {"requests": reading.requests,
                                  "tokens": reading.tokens,
                                  "seconds": check_s},
                      "policy_lazy": sorted(served.policy.lazy_names)}),
          flush=True)
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
