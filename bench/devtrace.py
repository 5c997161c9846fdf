"""Reduce a profiler trace of the window to device intervals.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
From it this module keeps, for the first chip (``/device:TPU:0``), every
XLA op and every XLA module execution, and from the host the bench's
own annotations (``bench.*``: ``traced`` spans the traced part of the
window, ``wait`` a wait for the next arrival, ``serve`` a dispatch to a
resident tenant, ``cold_start`` a dispatch that cold-starts one).  All
times are seconds on the trace's clock, clipped to the traced window.
"""

from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path

import numpy as np

DEVICE_PLANE = "/device:TPU:0"
HOST_PLANE = "/host:CPU"


@dataclass
class Trace:
    t0: float
    t1: float
    ops: list          # (name, start_s, end_s) of XLA ops on the device
    modules: list      # (name, start_s, end_s) of XLA program runs
    marks: list        # (name, start_s, end_s) of the bench's annotations

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def busy(self) -> np.ndarray:
        """The union of the op intervals: (n, 2) sorted, disjoint."""
        return union([(s, e) for _, s, e in self.ops])

    def busy_s(self) -> float:
        busy = self.busy()
        return float((busy[:, 1] - busy[:, 0]).sum())

    def marks_named(self, name: str) -> list:
        return [(s, e) for n, s, e in self.marks if n == name]


def op_name(event: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: an XLA op
    event is named by its whole HLO instruction."""
    return event.split(" = ", 1)[0].lstrip("%")


def step_programs(tr: Trace, records: list) -> dict:
    """The device runs of the prefill and the decode program.

    The served step programs are jitted partials, and their XLA modules
    carry no name of their own (``jit__unknown(<fingerprint>)``), so
    they are told apart by how often each runs inside a warm
    ``generate`` dispatch of ``n`` tokens: the decode program ``n - 1``
    times, the prefill program once; of the modules that do, the one
    with the most device time.  ``records`` are the window's requests
    in order; the traced dispatch annotations are their first ones.
    Returns {"prefill": [(start, end)], "decode": [(start, end)]}."""
    spans = sorted((s, e) for n, s, e in tr.marks
                   if n in ("bench.serve", "bench.cold_start"))
    votes = {"prefill": {}, "decode": {}}
    for (s, e), r in zip(spans, records):
        if r["path"] != "warm" or r["entry"] != "generate":
            continue
        runs: dict[str, list] = {}
        for n, a, b in tr.modules:
            if s <= a and b <= e:
                runs.setdefault(n, []).append(b - a)
        for n, d in runs.items():
            kind = {1: "prefill", r["new_tokens"] - 1: "decode"}.get(len(d))
            if kind:
                votes[kind][n] = votes[kind].get(n, 0.0) + sum(d)
    out = {}
    decode = max(votes["decode"], key=votes["decode"].get, default=None)
    votes["prefill"].pop(decode, None)
    prefill = max(votes["prefill"], key=votes["prefill"].get, default=None)
    for kind, name in (("prefill", prefill), ("decode", decode)):
        out[kind] = [(a, b) for n, a, b in tr.modules if n == name]
    return out


def union(intervals) -> np.ndarray:
    if not intervals:
        return np.zeros((0, 2))
    iv = np.array(sorted(intervals), float)
    out = [iv[0].copy()]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append(np.array([s, e]))
    return np.array(out)


def covered(busy: np.ndarray, s: float, e: float) -> float:
    """Seconds of [s, e] that ``busy`` covers."""
    if len(busy) == 0 or e <= s:
        return 0.0
    lo = np.clip(busy[:, 0], s, e)
    hi = np.clip(busy[:, 1], s, e)
    return float(np.sum(hi - lo))


def read(trace_dir: Path) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    ops, modules, marks = [], [], []
    for plane in data.planes:
        if plane.name == DEVICE_PLANE:
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is not None:
                    dest.extend((op_name(ev.name), ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9)
                                for ev in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                marks.extend((ev.name, ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
                             for ev in line.events
                             if ev.name.startswith("bench."))
    window = [(s, e) for n, s, e in marks if n == "bench.traced"]
    if not window:
        raise ValueError("trace has no bench.traced annotation")
    t0, t1 = window[0]

    def clip(evs):
        return [(n, max(s, t0), min(e, t1)) for n, s, e in evs
                if e > t0 and s < t1]

    return Trace(t0, t1, clip(ops), clip(modules),
                 [m for m in clip(marks) if m[0] != "bench.traced"])


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device ops that took most time, and the longest idle gaps,
    each named by the bench annotation the host was in at its middle."""
    per_op: dict[str, float] = {}
    for n, s, e in tr.ops:
        per_op[n] = per_op.get(n, 0.0) + (e - s)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    busy = tr.busy()
    edges = np.concatenate([[tr.t0], busy.ravel(), [tr.t1]]).reshape(-1, 2)
    gaps = []
    for s, e in edges:
        if e > s:
            mid = 0.5 * (s + e)
            tag = next((n.removeprefix("bench.") for n, a, b in tr.marks
                        if a <= mid <= b), "host")
            gaps.append([tag, float(e - s)])
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[n, float(v)] for n, v in ops],
            "idle_gaps": gaps[:top]}
