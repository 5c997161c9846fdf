"""Read the program's own instrumentation out of the window's trace.

``devtrace`` keeps the device's ops and program runs and the bench's
annotations.  From the same xplane this module keeps what the serving
program puts there itself:

* the spans of ``repro.obs.tracing`` that the profiler records as host
  events (``engine_*``, ``cold_start``, ``component:<name>``), each
  named by the event's name before any ``#``, with its attributes (the
  event's stats);
* the device's XLA ops with the scope path of the op's metadata
  (``jit(decode_next)/while/body/closed_call/moe/...``, from the
  ``jax.named_scope`` calls in ``models/model.py``), which the trace
  keeps in the ``tf_op`` stat of each op's event metadata.
  ``ProfileData`` does not show event metadata, so ``op_scopes`` reads
  it from the serialized XSpace itself.  An op name whose metadata
  entries carry two scopes is settled by the program the op runs in;
  one that stays ambiguous is left unscoped and counted.

Times are seconds on the trace's clock, clipped to the traced window.
The interval logic below is pure functions over ``(name, start, end)``
lists.
"""

from __future__ import annotations

import bisect
import functools
import statistics
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from devtrace import DEVICE_PLANE, HOST_PLANE, covered, op_name, union

# the tracer's span names as the profiler records them
SPAN_PREFIXES = ("engine_", "cold_start", "component:")
# the XLA op event-metadata stat that holds the op's op_name path
SCOPE_STAT = "tf_op"
# ops that only hold other ops: their time is their children's
CONTAINERS = frozenset({"while", "call", "conditional"})
SERVE = "engine_serve"
DECODE_LOOP = "engine_decode"
SERVE_SYNC = ("engine_prefill", "engine_route", "engine_readback",
              "engine_serve")


@dataclass
class ProgTrace:
    spans: list = field(default_factory=list)  # (name, start, end, attrs)
    ops: list = field(default_factory=list)    # (name, scope, start, end)
    ambiguous: int = 0  # ops left unscoped: their name has two scopes


def span_name(event: str) -> str:
    """``engine_serve#entry=generate#`` -> ``engine_serve``."""
    return event.split("#", 1)[0]


# ------------------------------------------------- XSpace wire format
# xplane.proto field numbers: XSpace.planes 1; XPlane.name 2,
# .event_metadata 4 and .stat_metadata 5 (map entries: key 1, value 2);
# XEventMetadata.name 2, .stats 5; XStatMetadata.name 2;
# XStat.metadata_id 1, .str_value 5, .ref_value 7
def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) of the message in ``buf[i:end]``: an int
    for a varint, a (start, end) slice for a length-delimited field,
    None for a fixed-width one."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        else:  # fixed64 (1) or fixed32 (5)
            value, i = None, i + (8 if wire == 1 else 4)
        yield key >> 3, value


def _text(buf, at) -> str:
    return bytes(buf[at[0]:at[1]]).decode("utf-8", "replace")


def op_scopes(buf: bytes, plane: str = DEVICE_PLANE,
              stat: str = SCOPE_STAT) -> dict:
    """{op event name: the sorted distinct ``stat`` values of its event
    metadata entries} over ``plane`` in the serialized XSpace ``buf``,
    with the ``:<type>`` suffix of a ``tf_op`` cut off.  Two programs
    can hold an op of the same name; the tuple then has both scopes."""
    for num, at in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        fields = [(n, v) for n, v in _fields(buf, *at) if n in (2, 4, 5)]
        if [_text(buf, v) for n, v in fields if n == 2] != [plane]:
            continue
        stat_names = {}
        for n, v in fields:
            if n == 5:
                entry = dict(_fields(buf, *v))
                meta = dict(_fields(buf, *entry[2]))
                stat_names[entry.get(1, 0)] = _text(buf, meta.get(2, (0, 0)))
        out: dict[str, set] = {}
        for n, v in fields:
            if n != 4:
                continue
            meta = list(_fields(buf, *dict(_fields(buf, *v))[2]))
            name = next((_text(buf, x) for m, x in meta if m == 2), "")
            for m, x in meta:
                if m != 5:
                    continue
                st = dict(_fields(buf, *x))
                if stat_names.get(st.get(1, 0)) == stat:
                    got = (_text(buf, st[5]) if 5 in st
                           else stat_names.get(st.get(7), ""))
                    out.setdefault(name, set()).add(got.rsplit(":", 1)[0])
        return {name: tuple(sorted(got)) for name, got in out.items()}
    return {}


def pick_scope(scopes: tuple, module: str | None) -> str | None:
    """The one of an op's ``scopes`` that belongs to the XLA module it
    ran in (``jit_decode_next(7)`` holds scopes that start
    ``jit(decode_next)``); "" without a scope, None when that leaves
    no single one."""
    if len(scopes) <= 1:
        return scopes[0] if scopes else ""
    if module is None or not module.startswith("jit_"):
        return None
    prefix = "jit(%s)" % module.split("(", 1)[0][len("jit_"):]
    mine = [c for c in scopes if c.split("/", 1)[0] == prefix]
    return mine[0] if len(mine) == 1 else None


def module_at(modules: list, starts: list, t: float) -> str | None:
    """The name of the module run in ``modules`` [(name, start, end)],
    sorted by start with ``starts`` their starts, that holds ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    return modules[i][0] if i >= 0 and t <= modules[i][2] else None


@functools.lru_cache(maxsize=4)
def _parse(xplane: str) -> ProgTrace:
    from jax.profiler import ProfileData
    buf = Path(xplane).read_bytes()
    scopes = op_scopes(buf)
    data = ProfileData.from_serialized_xspace(buf)
    out = ProgTrace()
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    name = span_name(ev.name)
                    if name.startswith(SPAN_PREFIXES):
                        out.spans.append((
                            name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9,
                            dict(ev.stats)))
        elif plane.name == DEVICE_PLANE:
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                ((ev.name, ev.start_ns * 1e-9,
                  (ev.start_ns + ev.duration_ns) * 1e-9)
                 for ev in getattr(lines.get("XLA Modules"), "events", ())),
                key=lambda m: m[1])
            starts = [s for _, s, _ in modules]
            for ev in getattr(lines.get("XLA Ops"), "events", ()):
                s = ev.start_ns * 1e-9
                got = scopes.get(ev.name, ())
                scope = pick_scope(
                    got, module_at(modules, starts, s) if len(got) > 1
                    else None)
                if scope is None:
                    out.ambiguous += 1
                    scope = ""
                out.ops.append((op_name(ev.name), scope, s,
                                s + ev.duration_ns * 1e-9))
    return out


def read(trace_dir: Path, t0: float, t1: float) -> ProgTrace:
    """The program's spans and scoped device ops in the newest trace
    under ``trace_dir``, clipped to [t0, t1] (the parse is memoised per
    file)."""
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    full = _parse(str(files[-1]))
    return ProgTrace(
        [(n, max(s, t0), min(e, t1), a) for n, s, e, a in full.spans
         if e > t0 and s < t1],
        [(n, c, max(s, t0), min(e, t1)) for n, c, s, e in full.ops
         if e > t0 and s < t1],
        full.ambiguous)


def of(run) -> ProgTrace | None:
    """The program's part of ``run``'s trace, or None untraced."""
    if run.trace is None:
        return None
    import harness
    return read(harness.TRACE_DIR, run.trace.t0, run.trace.t1)


# ------------------------------------------------------ pure interval logic
def in_scope(scope: str, name: str) -> bool:
    """Whether ``name`` is one of the components of a scope path."""
    return name in scope.split("/")


def scope_seconds(ops: list, runs: list, scope: str) -> float | None:
    """Mean, over program ``runs`` [(start, end)], of the union of the
    intervals of device ops in ``scope`` (``ops``: (name, scope, start,
    end)) within the run.  Container ops are left out; the union counts
    nested ops once.  None without runs."""
    if not runs:
        return None
    mine = [(s, e) for n, c, s, e in ops
            if in_scope(c, scope) and n.split(".")[0] not in CONTAINERS]
    busy = union(mine)
    return sum(covered(busy, s, e) for s, e in runs) / len(runs)


def runs_of(modules: list, program: str) -> list:
    """[(start, end)] of the runs of the XLA module ``program`` (a
    module is named ``<program>(<id>)``)."""
    return [(s, e) for n, s, e in modules if n.split("(", 1)[0] == program]


def idle_gaps(busy: np.ndarray, s: float, e: float) -> list:
    """[(start, end)] of [s, e] that ``busy`` (sorted, disjoint) leaves
    uncovered."""
    gaps, cur = [], s
    for a, b in busy:
        if b <= cur:
            continue
        if a >= e:
            break
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < e:
        gaps.append((cur, e))
    return gaps


def innermost(spans: list, t: float) -> str | None:
    """The name of the innermost span open at ``t``: of those that
    hold it, the one that started last (spans on one thread nest)."""
    best = None
    for n, s, e, *_ in spans:
        if s <= t <= e and (best is None or s > best[1]
                            or (s == best[1] and e < best[2])):
            best = (n, s, e)
    return best[0] if best else None


def idle_by_span(serves: list, spans: list, busy: np.ndarray) -> dict:
    """Device-idle seconds inside each of ``serves`` [(start, end)]:
    each instant of a gap goes to the innermost of ``spans`` open then.
    A gap that straddles a span's edge is split there, so a small
    offset between the host's and the device's clocks moves a little
    idle across the edge rather than the whole gap."""
    out: dict[str, float] = {}
    for s, e in serves:
        inside = [sp for sp in spans if s <= sp[1] and sp[2] <= e]
        edges = sorted({t for sp in inside for t in sp[1:3]})
        for a, b in idle_gaps(busy, s, e):
            cuts = [a, *(t for t in edges if a < t < b), b]
            for x, y in zip(cuts, cuts[1:]):
                name = innermost(inside, 0.5 * (x + y)) or SERVE
                out[name] = out.get(name, 0.0) + (y - x)
    return out


def warm_generates(spans: list, warm_marks: list, t0: float,
                   t1: float) -> list:
    """The ``engine_serve`` spans of ``generate`` requests that lie in
    the traced window (a span the window cuts is clipped to its edge)
    and inside a warm dispatch (``warm_marks`` [(start, end)]):
    [(start, end, new_tokens)]."""
    out = []
    for n, s, e, attrs in spans:
        if n != SERVE or str(attrs.get("entry")) != "generate":
            continue
        if t0 < s and e < t1 and any(a <= s and e <= b
                                     for a, b in warm_marks):
            out.append((s, e, int(attrs["new_tokens"])))
    return out


def idle_per_request(run) -> list | None:
    """[(device-idle seconds by innermost program span, output tokens)]
    of each warm ``generate`` request in the traced window, or None
    without such requests (a program that opens no spans)."""
    prog = of(run)
    if prog is None:
        return None
    tr = run.trace
    serves = warm_generates(prog.spans, tr.marks_named("bench.serve"),
                            tr.t0, tr.t1)
    if not serves:
        return None
    busy = tr.busy()
    return [(idle_by_span([(s, e)], prog.spans, busy), n)
            for s, e, n in serves]


def pooled_idle_ms(requests: list, names) -> float:
    """The idle given to ``names`` over all ``requests``, in ms per
    output token."""
    idle = sum(by.get(n, 0.0) for by, _ in requests for n in names)
    return idle / sum(n for _, n in requests) * 1e3


def median_idle_ms(requests: list, names) -> float:
    """The median over ``requests`` of each one's idle given to
    ``names``, in ms per its output token: one long host stall moves it
    by at most one rank."""
    return statistics.median(
        sum(by.get(n, 0.0) for n in names) / tokens * 1e3
        for by, tokens in requests)


def decode_scope_ms(run, scope: str) -> float | None:
    """Mean device ms of the ``scope`` ops in one ``jit_decode_next``
    run, or None without such runs or ops."""
    prog = of(run)
    if prog is None or not any(in_scope(c, scope) for _, c, _, _ in prog.ops):
        return None
    runs = runs_of(run.trace.modules, "jit_decode_next")
    got = scope_seconds(prog.ops, runs, scope)
    return None if got is None else got * 1e3
