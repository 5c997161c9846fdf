"""Cold-start anatomy: turn a bag of spans into an attribution story.

The model: every request is one *trace* whose root span ("request")
measures end-to-end wall time.  Child spans (queue_wait, dispatch,
fork, import, import:<module>, invoke, cold_start, ...) partition that
time; whatever the children don't cover is the root's *self time* and
shows up as ``(unattributed)`` so the per-phase table always sums to
the measured end-to-end latency — the acceptance bar is that the
unattributed share stays small.

Outputs:

* :func:`phase_breakdown` — per-phase count / p50 / p99 / total self
  time / share-of-wall, plus overall attribution coverage.
* :func:`top_imports` — slowest ``import:*`` spans (per-module, keyed
  by cumulative init with self time alongside).
* :func:`folded_stacks` — ``root;child;leaf value`` lines compatible
  with Brendan Gregg's ``flamegraph.pl`` (values in microseconds of
  span *self* time).
* :func:`render_report` — the human table ``repro obs report`` prints.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence

from repro.obs.tracing import Span

__all__ = [
    "build_traces",
    "phase_breakdown",
    "top_imports",
    "folded_stacks",
    "render_report",
    "UNATTRIBUTED",
]

UNATTRIBUTED = "(unattributed)"

# Stable presentation order for the well-known lifecycle phases; any
# other span name sorts after these, alphabetically.
_PHASE_ORDER = ["request", "enqueue", "queue_wait", "dispatch",
                "zygote_boot", "spawn_app", "preload", "fork", "import",
                "invoke", "cold_start", "engine_cold_start",
                "engine_serve", "engine_prefill", "engine_route",
                "engine_decode", "engine_readback", UNATTRIBUTED]


def _coerce(spans: Iterable) -> List[Span]:
    out = []
    for s in spans:
        out.append(s if isinstance(s, Span) else Span.from_dict(s))
    return out


class TraceTree:
    """One trace: its spans, child index and computed self times."""

    def __init__(self, trace_id: str, spans: List[Span]):
        self.trace_id = trace_id
        self.spans = spans
        self.by_id = {s.span_id: s for s in spans}
        self.children: Dict[str, List[Span]] = defaultdict(list)
        self.roots: List[Span] = []
        for s in spans:
            if s.parent_id and s.parent_id in self.by_id:
                self.children[s.parent_id].append(s)
            else:
                self.roots.append(s)

    def self_ms(self, span: Span) -> float:
        kids = sum(c.duration_ms for c in self.children[span.span_id])
        return max(0.0, span.duration_ms - kids)

    @property
    def root(self) -> Optional[Span]:
        # Prefer an explicit request root; else the longest top-level.
        named = [s for s in self.roots if s.name == "request"]
        pool = named or self.roots
        return max(pool, key=lambda s: s.duration_ms) if pool else None


def build_traces(spans: Iterable) -> List[TraceTree]:
    groups: Dict[str, List[Span]] = defaultdict(list)
    for s in _coerce(spans):
        groups[s.trace_id].append(s)
    return [TraceTree(tid, ss) for tid, ss in groups.items()]


def _percentile(values: Sequence[float], q: float) -> float:
    if not values:
        return 0.0
    vs = sorted(values)
    idx = min(len(vs) - 1, int(round(q * (len(vs) - 1))))
    return vs[idx]


def _phase_name(span: Span) -> str:
    # Per-module import spans roll up into the "import" phase for the
    # breakdown table; top_imports keeps them individual.
    if span.name.startswith("import:"):
        return "import"
    if span.name.startswith("preload:"):
        return "preload"
    return span.name


def phase_breakdown(spans: Iterable) -> dict:
    """Aggregate self time per phase across every complete trace.

    Returns ``{"phases": [row...], "requests": n,
    "wall_ms_total": t, "attributed_frac": f}`` where each row has
    ``phase, count, p50_ms, p99_ms, total_ms, share`` and rows sum
    (by construction, via the unattributed residual) to the wall time.
    """
    traces = [t for t in build_traces(spans) if t.root is not None]
    per_phase_self: Dict[str, List[float]] = defaultdict(list)
    per_phase_dur: Dict[str, List[float]] = defaultdict(list)
    wall_total = 0.0
    request_wall = 0.0
    n_requests = 0
    for tree in traces:
        root = tree.root
        wall_total += root.duration_ms
        is_request = root.name == "request"
        if is_request:
            n_requests += 1
            request_wall += root.duration_ms
        for s in tree.spans:
            if s is root:
                continue
            phase = _phase_name(s)
            per_phase_self[phase].append(tree.self_ms(s))
            per_phase_dur[phase].append(s.duration_ms)
        resid = tree.self_ms(root)
        if is_request:
            per_phase_self[UNATTRIBUTED].append(resid)
            per_phase_dur[UNATTRIBUTED].append(resid)
        else:
            # a non-request trace (zygote_boot / spawn_app) *is* its
            # own phase: its residual is that phase's self time, not
            # unexplained request latency
            per_phase_self[_phase_name(root)].append(resid)
            per_phase_dur[_phase_name(root)].append(root.duration_ms)

    def order(name: str):
        try:
            return (0, _PHASE_ORDER.index(name))
        except ValueError:
            return (1, name)

    rows = []
    for phase in sorted(per_phase_self, key=order):
        self_ms = per_phase_self[phase]
        durs = per_phase_dur[phase]
        rows.append({
            "phase": phase,
            "count": len(durs),
            "p50_ms": round(_percentile(durs, 0.50), 3),
            "p99_ms": round(_percentile(durs, 0.99), 3),
            "total_ms": round(sum(self_ms), 3),
            "share": round(sum(self_ms) / wall_total, 4)
            if wall_total else 0.0,
        })
    unattr = sum(per_phase_self.get(UNATTRIBUTED, []))
    return {
        "requests": n_requests,
        "traces": len(traces),
        "wall_ms_total": round(wall_total, 3),
        "request_wall_ms": round(request_wall, 3),
        "attributed_frac": round(1.0 - (unattr / wall_total), 4)
        if wall_total else 1.0,
        "phases": rows,
    }


def top_imports(spans: Iterable, n: int = 10) -> List[dict]:
    """Slowest modules by cumulative init across all traces."""
    agg: Dict[str, dict] = {}
    for s in _coerce(spans):
        if not s.name.startswith("import:"):
            continue
        mod = s.attrs.get("module") or s.name[len("import:"):]
        row = agg.setdefault(mod, {"module": mod, "count": 0,
                                   "cumulative_ms": 0.0, "self_ms": 0.0})
        row["count"] += 1
        row["cumulative_ms"] += s.duration_ms
        row["self_ms"] += float(s.attrs.get("self_ms", s.duration_ms))
    out = sorted(agg.values(), key=lambda r: -r["cumulative_ms"])[:n]
    for row in out:
        row["cumulative_ms"] = round(row["cumulative_ms"], 3)
        row["self_ms"] = round(row["self_ms"], 3)
    return out


def folded_stacks(spans: Iterable) -> List[str]:
    """``frame;frame;frame value`` lines for flamegraph.pl.

    One line per span, path from the trace root down, value = span
    self time in integer microseconds (zero-valued frames are kept out
    to match flamegraph.pl expectations).
    """
    counts: Dict[str, int] = defaultdict(int)
    for tree in build_traces(spans):
        for s in tree.spans:
            path: List[str] = []
            cur: Optional[Span] = s
            seen = set()
            while cur is not None and cur.span_id not in seen:
                seen.add(cur.span_id)
                path.append(cur.name.replace(";", ":"))
                cur = tree.by_id.get(cur.parent_id or "")
            us = int(round(tree.self_ms(s) * 1000))
            if us > 0:
                counts[";".join(reversed(path))] += us
    return [f"{path} {us}" for path, us in sorted(counts.items())]


def render_report(spans: Iterable, *, top_n: int = 10,
                  meta: Optional[dict] = None) -> str:
    """Human-readable cold-start anatomy report."""
    from repro.api.render import table

    breakdown = phase_breakdown(spans)
    lines: List[str] = []
    lines.append("cold-start anatomy")
    if meta:
        src = ", ".join(f"{k}={v}" for k, v in sorted(meta.items())
                        if not isinstance(v, (dict, list)))
        if src:
            lines.append(f"  source: {src}")
    n = breakdown["requests"]
    wall = breakdown["wall_ms_total"]
    req_wall = breakdown["request_wall_ms"]
    lines.append(
        f"  requests: {n} (of {breakdown['traces']} traces)   "
        f"wall: {wall:.1f} ms total"
        + (f" ({req_wall / n:.2f} ms/req)" if n else ""))
    lines.append(
        f"  attributed: {breakdown['attributed_frac'] * 100:.1f}% of "
        "end-to-end time is covered by child spans")
    lines.append("")
    lines.append(table(
        [{"phase": r["phase"], "count": r["count"],
          "p50 ms": f"{r['p50_ms']:.2f}",
          "p99 ms": f"{r['p99_ms']:.2f}",
          "total ms": f"{r['total_ms']:.1f}",
          "share": f"{r['share'] * 100:.1f}%"}
         for r in breakdown["phases"]],
        ["phase", "count", "p50 ms", "p99 ms", "total ms", "share"]))
    imports = top_imports(spans, n=top_n)
    if imports:
        lines.append("")
        lines.append(f"top {len(imports)} slowest imports "
                     "(cumulative module init):")
        lines.append(table(
            [{"module": r["module"], "count": r["count"],
              "cum ms": f"{r['cumulative_ms']:.2f}",
              "self ms": f"{r['self_ms']:.2f}"} for r in imports],
            ["module", "count", "cum ms", "self ms"]))
    return "\n".join(lines)
