"""The serving path's spans, step-program names and device scopes: the
tracer's two sinks (ring buffer, JAX profiler), the ``engine_*`` and
``component:*`` spans of a reduced engine, ``jit_<step>`` module names
and ``named_scope`` paths in the lowered programs."""

import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.obs.tracing import Tracer, _NOOP, configure_tracing, get_tracer
from repro.serving import EnginePool, LoadPolicy, ServingEngine

SRC = str(Path(__file__).resolve().parents[1] / "src")


@pytest.fixture(autouse=True)
def _tracing_off():
    configure_tracing(enabled=False)
    get_tracer().clear()
    yield
    configure_tracing(enabled=False)
    get_tracer().clear()


def host_events(trace_dir) -> list:
    """(name, start_ns, end_ns, stats) of every host event in the trace
    under ``trace_dir``; a name is cut at its first ``#``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    data = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((ev.name.split("#", 1)[0], ev.start_ns,
                        ev.start_ns + ev.duration_ns, dict(ev.stats))
                       for ev in line.events)
    return out


def named(events, name):
    return [e for e in events if e[0] == name]


def inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


# ------------------------------------------------------------ the sinks
@pytest.mark.parametrize("ring,profiler", [
    (False, False), (False, True), (True, False), (True, True)])
def test_tracer_sinks(tmp_path, ring, profiler):
    tr = Tracer(enabled=ring)
    if profiler:
        jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("sink_probe", app="a") as h:
            h.set("path", "warm")
    finally:
        if profiler:
            jax.profiler.stop_trace()
    if not ring and not profiler:
        assert h is _NOOP
    assert bool(h) is ring
    spans = tr.snapshot()
    assert [s.name for s in spans] == (["sink_probe"] if ring else [])
    if ring:
        assert spans[0].attrs == {"app": "a", "path": "warm"}
    if profiler:
        (ev,) = named(host_events(tmp_path), "sink_probe")
        assert ev[3]["app"] == "a" and ev[3]["path"] == "warm"


def test_tracer_check_never_imports_jax():
    code = ("import sys\n"
            "from repro.obs.tracing import get_tracer\n"
            "with get_tracer().span('x'):\n"
            "    pass\n"
            "print('jax' in sys.modules)\n")
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "False"


# ------------------------------------------------- the serving path's spans
def _engine(arch, policy=None):
    return ServingEngine(get_reduced(arch), policy=policy, batch_size=1,
                         prefill_len=8, max_len=24)


@pytest.fixture(scope="module")
def warm_engines():
    out = {}
    for arch in ("granite-moe-1b-a400m", "granite-8b"):
        eng = _engine(arch)
        eng.cold_start()
        out[arch] = eng
    return out


@pytest.mark.parametrize("arch,moe", [("granite-moe-1b-a400m", True),
                                      ("granite-8b", False)])
def test_serve_spans_nest_in_the_profiler_trace(tmp_path, warm_engines,
                                                arch, moe):
    eng = warm_engines[arch]
    toks = np.random.default_rng(0).integers(0, eng.cfg.vocab, (1, 8))
    eng.serve("generate", toks, max_new_tokens=4)  # nothing left to build
    jax.profiler.start_trace(str(tmp_path))
    try:
        out, _ = eng.serve("generate", toks, max_new_tokens=4)
    finally:
        jax.profiler.stop_trace()
    assert out.shape == (1, 4)
    evs = host_events(tmp_path)
    (serve,) = named(evs, "engine_serve")
    assert serve[3]["entry"] == "generate" and serve[3]["new_tokens"] == 4
    for child in ("engine_prefill", "engine_decode", "engine_readback"):
        (ev,) = named(evs, child)
        assert inside(ev, serve), child
    assert named(evs, "engine_decode")[0][3]["steps"] == 3
    route = named(evs, "engine_route")
    assert len(route) == (1 if moe else 0)
    if moe:
        assert inside(route[0], serve)
    assert not [e for e in evs if e[0].startswith("component:")]


def test_cold_start_spans_nest_under_the_pool():
    configure_tracing(enabled=True)
    pool = EnginePool({"m": lambda: _engine(
        "granite-moe-1b-a400m",
        LoadPolicy(lazy_names=frozenset({"compile.score"})))}, max_warm=1)
    toks = np.zeros((1, 8), np.int32)
    pool.dispatch("m", "generate", toks, max_new_tokens=3)   # cold
    n_before = len(get_tracer().snapshot())
    pool.dispatch("m", "generate", toks, max_new_tokens=3)   # warm
    spans = get_tracer().snapshot()
    by_id = {s.span_id: s for s in spans}

    def parent(s):
        return by_id[s.parent_id].name

    (cold,) = [s for s in spans if s.name == "cold_start"]
    (engine_cold,) = [s for s in spans if s.name == "engine_cold_start"]
    assert parent(engine_cold) == "cold_start"
    assert parent(cold) == "engine_dispatch"
    comps = [s for s in spans[:n_before] if s.name.startswith("component:")]
    names = {s.name for s in comps}
    assert {"component:weights.core", "component:compile.generate",
            "component:expert.0"} <= names
    assert "component:compile.score" not in names  # deferred
    for s in comps:
        assert parent(s) in ("engine_cold_start", "engine_route"), s.name
    serves = [s for s in spans if s.name == "engine_serve"]
    assert [parent(s) for s in serves] == ["engine_dispatch"] * 2
    for name in ("engine_prefill", "engine_route", "engine_decode",
                 "engine_readback"):
        kids = [s for s in spans if s.name == name]
        assert [by_id[s.parent_id] for s in kids] == serves, name
    # a warm get builds nothing, so it opens no component span
    assert not [s for s in spans[n_before:]
                if s.name.startswith("component:")]


def test_lazy_compile_opens_its_component_span_under_serve():
    configure_tracing(enabled=True)
    eng = _engine("granite-8b", LoadPolicy(lazy_names=frozenset(
        {"compile.score"})))
    eng.cold_start()
    get_tracer().clear()
    with get_tracer().span("request") as root:
        eng.serve("score", np.zeros((1, 8), np.int32), ctx=root.ctx())
    spans = {s.name: s for s in get_tracer().snapshot()}
    assert spans["engine_serve"].parent_id == spans["request"].span_id
    assert spans["component:compile.score"].parent_id == \
        spans["engine_serve"].span_id
    assert spans["engine_serve"].attrs == {"entry": "score",
                                           "new_tokens": 0}


@pytest.mark.parametrize("arch,entry,path", [
    ("granite-moe-1b-a400m", "generate", "routed"),  # its decode step
    ("granite-moe-1b-a400m", "score", "capacity"),
    ("granite-8b", "generate", None),
])
def test_compile_span_names_the_moe_path(arch, entry, path):
    configure_tracing(enabled=True)
    _engine(arch).cold_start()
    (span,) = [s for s in get_tracer().snapshot()
               if s.name == f"component:compile.{entry}"]
    assert span.attrs.get("moe_path") == path


# ------------------------------------------ step programs and device scopes
def test_step_programs_compile_under_their_own_names():
    eng = _engine("granite-moe-1b-a400m")
    progs = {**eng.entry_programs("generate"), **eng.entry_programs("score")}
    names = {k: fn.lower(*args).compile().as_text().split(",")[0]
             for k, (fn, args) in progs.items()}
    assert names == {"prefill": "HloModule jit_prefill_step",
                     "decode": "HloModule jit_decode_next",
                     "score": "HloModule jit_score_step"}


def scopes(fn, args) -> set:
    """Every component of the op_name paths in the lowered HLO's
    metadata."""
    text = fn.lower(*args).as_text("hlo", debug_info=True)
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in name.split("/")}


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_device_scopes_in_lowered_metadata(program):
    eng = _engine("granite-moe-1b-a400m")
    got = scopes(*eng.entry_programs("generate")[program])
    assert {"moe", "attn", "head"} <= got
    assert "mlp" not in got


def test_dense_blocks_scope_their_ff_as_mlp():
    eng = _engine("granite-8b")
    got = scopes(*eng.entry_programs("generate")["decode"])
    assert {"mlp", "attn", "head"} <= got and "moe" not in got
