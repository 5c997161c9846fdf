"""Readings the benchmark's fixed numbers were set from, each in one
process on the chip (the benchmark's own runs never run this):

    python3 bench/calibrate.py sweep --workload <cell> --seed <n> \\
        --seconds 20 --rates 2,3,4
    python3 bench/calibrate.py control --workload <cell> --seed <n> \\
        --seconds 15 --seeds 12 --control-seeds 3

``sweep`` serves the cell's mix at each rate for ``--seconds`` after one
set-up and prints, per rate, the latency quantiles and whether the
backlog grew (the mean wait of the window's last third against its
first, and how long the pool took to drain after the last arrival).
The cell's rate is four fifths of the highest rate that keeps up.

``control`` reads, on ``--seeds`` seeds, what a run's correctness check
reads (the widest logit gap of the served tokens and the relative
errors of the served ``score`` logits position by position, given at
several quantiles), after a window at the cell's own load;
on the first ``--control-seeds`` of them it also reads the control, the
reference computed with the configuration's lower precision in the
program's place, and the gap of the served tokens altered to the next
id, and the harness's verdict (``check.verdict``) on the control put in
the program's place, under the cell's own limits.  The limits in
``configs/<config>.json`` lie between the two.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402

import harness  # noqa: E402

QUANTILES = (0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0)


def emit(**kw):
    print(json.dumps(kw), flush=True)


def start(name: str):
    cell = harness.load_cell(name)
    harness.pin_compile_cache()
    harness.chip_or_exit(cell.chips, harness.load_peaks())
    return cell, harness.program_config(cell.spec)


def sweep(args) -> None:
    cell, cfg = start(args.workload)
    served = harness.set_up(cell, cfg, args.seed)
    traffic = harness.traffic_module(cell.mix)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate=rate)
        sched = traffic.schedule(mix, harness.subseed(args.seed, 1),
                                 args.seconds, cfg.vocab)
        win = harness.drive(served, sched)
        lat = np.array([r["end"] - r["due"] for r in win.records])
        wait = np.array([r["start"] - r["due"] for r in win.records])
        third = max(len(wait) // 3, 1)
        emit(rate=rate, requests=len(lat),
             p50_ms=float(np.percentile(lat, 50)) * 1e3,
             p95_ms=float(np.percentile(lat, 95)) * 1e3,
             wait_first_third_ms=float(wait[:third].mean()) * 1e3,
             wait_last_third_ms=float(wait[-third:].mean()) * 1e3,
             drain_s=win.seconds - sched[-1]["due"],
             cold=sum(r["path"] == "cold" for r in win.records),
             failed=sum(r["path"] == "failed" for r in win.records))


def control(args) -> None:
    import check
    cell, cfg = start(args.workload)
    served = harness.set_up(cell, cfg, args.seed)
    traffic = harness.traffic_module(cell.mix)
    harness.free(served)
    for k in range(args.seeds):
        seed = args.seed + 1 + k
        pool, tenants = harness.make_pool(cell, cfg, served.policy, seed)
        run = harness.Served(pool, tenants, served.policy, 0.0)
        sched = traffic.schedule(cell.mix, harness.subseed(seed, 1),
                                 args.seconds, cfg.vocab)
        win = harness.drive(run, sched)
        harness.free(run)
        picked = check.sample(win.records, seed)
        ctl = cell.spec["check"]["control"] if k < args.control_seeds \
            else None
        t = time.perf_counter()
        rd = check.read(cell.spec, tenants, picked, control=ctl)
        failed = sum(r["path"] == "failed" for r in win.records)
        verdict = {"program": rd}
        if ctl:
            verdict["control"] = check.control_in_place(rd)
        emit(seed=seed, gap=rd.widest_gap,
             err={q: check.score_err(rd.errs, q) for q in QUANTILES},
             control=ctl, control_gap=rd.control_gap,
             control_err={q: check.score_err(rd.control_errs, q)
                          for q in QUANTILES},
             altered_gap=rd.altered_gap,
             correct={k: check.is_correct(
                 check.verdict(cell.spec, v, failed), v)
                 for k, v in verdict.items()},
             check_s=time.perf_counter() - t, tokens=rd.tokens,
             requests=rd.requests,
             cold=sum(r["path"] == "cold" for r in win.records),
             failed=failed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name in ("sweep", "control"):
        p = sub.add_parser(name)
        p.add_argument("--workload", required=True)
        p.add_argument("--seed", type=int, required=True)
        p.add_argument("--seconds", type=float, required=True)
    sub.choices["sweep"].add_argument("--rates", required=True)
    sub.choices["control"].add_argument("--seeds", type=int, default=12)
    sub.choices["control"].add_argument("--control-seeds", type=int,
                                        default=3)
    args = ap.parse_args(argv)
    {"sweep": sweep, "control": control}[args.cmd](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
