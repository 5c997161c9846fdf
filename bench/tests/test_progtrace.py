"""progtrace: the program's spans and scoped device ops, on synthetic
intervals and on a profiler trace recorded on the CPU."""

import jax
import pytest

import devtrace
import harness
import progtrace
from progtrace import ProgTrace
from run import Run
from test_metrics import PEAKS, SPEC


def test_scope_union_skips_containers_and_counts_nested_ops_once():
    ops = [
        ("while.1", "jit(decode_next)/while", 1.00, 1.10),
        ("fusion.1", "jit(decode_next)/while/body/moe/dot", 1.01, 1.05),
        ("fusion.2", "jit(decode_next)/while/body/moe/add", 1.02, 1.04),
        ("call.3", "jit(decode_next)/while/body/moe", 1.00, 1.09),
        ("fusion.4", "jit(decode_next)/while/body/attn/dot", 1.06, 1.08),
        ("fusion.5", "jit(decode_next)/while/body/moe_gate/x", 1.08, 1.09),
        ("fusion.6", "jit(prefill_step)/while/body/moe/dot", 2.0, 2.5),
    ]
    runs = progtrace.runs_of([("jit_decode_next(7)", 1.0, 1.1),
                              ("jit_prefill_step(3)", 2.0, 2.6),
                              ("jit_decode_next(7)", 3.0, 3.1)],
                             "jit_decode_next")
    assert runs == [(1.0, 1.1), (3.0, 3.1)]
    # 0.04 s of moe ops in the first run, none in the second
    assert progtrace.scope_seconds(ops, runs, "moe") == pytest.approx(0.02)
    assert progtrace.scope_seconds(ops, runs, "attn") == pytest.approx(0.01)
    assert progtrace.scope_seconds(ops, [], "moe") is None


def test_idle_goes_to_the_innermost_span_open_at_each_instant():
    spans = [("engine_serve", 0.0, 1.0, {}),
             ("engine_decode", 0.2, 0.9, {}),
             ("component:expert.3", 0.3, 0.4, {})]
    busy = devtrace.union([(0.1, 0.25), (0.5, 0.6)])
    assert progtrace.idle_gaps(busy, 0.0, 1.0) == [
        (0.0, 0.1), (0.25, 0.5), (0.6, 1.0)]
    idle = progtrace.idle_by_span([(0.0, 1.0)], spans, busy)
    # 0-0.1 serve; 0.25-0.5 split at the component's edges: 0.25-0.3
    # and 0.4-0.5 decode, 0.3-0.4 the component; 0.6-1.0 split at the
    # decode span's end: 0.6-0.9 decode, 0.9-1.0 serve
    assert idle == pytest.approx({"engine_serve": 0.2,
                                  "component:expert.3": 0.1,
                                  "engine_decode": 0.45})
    assert progtrace.innermost(spans, 1.5) is None


def served_run():
    """A traced window with a cold generate, two warm generates (4 and
    2 tokens), a warm score and a warm generate cut by the window's
    end; the device's ops; the program's spans."""
    ops = [(0.105, 0.118), (0.131, 0.145), (0.147, 0.160), (0.162, 0.185),
           (0.31, 0.33), (0.335, 0.35),
           (0.02, 0.05), (0.41, 0.44), (0.91, 0.95)]
    tr = devtrace.Trace(
        t0=0.0, t1=1.0,
        ops=[("fusion.%d" % i, s, e) for i, (s, e) in enumerate(ops)],
        modules=[],
        marks=[("bench.cold_start", 0.0, 0.06),
               ("bench.serve", 0.095, 0.205), ("bench.serve", 0.295, 0.37),
               ("bench.serve", 0.40, 0.45), ("bench.serve", 0.90, 1.0)])
    spans = [
        ("engine_serve", 0.01, 0.055, {"entry": "generate",
                                       "new_tokens": 8}),
        ("engine_serve", 0.10, 0.20, {"entry": "generate",
                                      "new_tokens": 4}),
        ("engine_prefill", 0.10, 0.12, {}),
        ("engine_route", 0.12, 0.13, {}),
        ("engine_decode", 0.13, 0.19, {"steps": 3}),
        ("engine_readback", 0.19, 0.20, {}),
        ("engine_serve", 0.30, 0.36, {"entry": "generate",
                                      "new_tokens": 2}),
        ("engine_prefill", 0.30, 0.33, {}),
        ("engine_decode", 0.33, 0.352, {"steps": 1}),
        ("engine_readback", 0.352, 0.36, {}),
        ("engine_serve", 0.40, 0.45, {"entry": "score", "new_tokens": 0}),
        ("engine_serve", 0.90, 1.0, {"entry": "generate",
                                     "new_tokens": 16}),
    ]
    window = harness.Window()
    window.seconds = window.traced_s = 1.0
    run = Run(SPEC, PEAKS, {}, window, None, tr)
    return run, ProgTrace(spans, [])


@pytest.fixture
def program(monkeypatch):
    run, prog = served_run()
    monkeypatch.setattr(progtrace, "of", lambda r: prog)
    return run, prog


def test_idle_metrics_per_output_token(program):
    run, _ = program
    # first warm generate: prefill 0.10-0.105 and 0.118-0.12, route
    # 0.12-0.13, decode 0.13-0.131, 0.145-0.147, 0.160-0.162 and
    # 0.185-0.19, readback 0.19-0.20; second: prefill 0.30-0.31, decode
    # 0.33-0.335 and 0.35-0.352, readback 0.352-0.36; 4 + 2 tokens (the
    # score, the cold one and the cut one left out)
    # decode idle 0.010 s over 4 tokens and 0.007 s over 2: the median
    # request's rate is their mean, 3.0 ms/token; pooled, 2.833
    decode = (0.010 / 4 + 0.007 / 2) / 2 * 1e3
    pooled = (0.010 + 0.007) / 6 * 1e3
    sync = (0.005 + 0.002 + 0.010 + 0.010 + 0.010 + 0.008) / 6 * 1e3
    for name, want in (("decode_loop_idle_ms", decode),
                       ("decode_loop_stall_ms", pooled - decode),
                       ("serve_sync_idle_ms", sync)):
        got = harness.metric_module(name).read(run)
        assert got == pytest.approx(want), name


def test_one_stall_moves_the_stall_reader_not_the_median():
    loop = progtrace.DECODE_LOOP
    steady = [({loop: 0.001 * n, "engine_readback": 0.002}, n)
              for n in (8, 16, 32, 64, 128)]
    stalled = steady[:4] + [({loop: 0.001 * 128 + 2.1,
                              "engine_readback": 0.002}, 128)]
    tokens = 8 + 16 + 32 + 64 + 128
    for requests in (steady, stalled):
        # each request idles 1 ms per token in its loop
        assert progtrace.median_idle_ms(requests, (loop,)) == \
            pytest.approx(1.0)
    assert progtrace.pooled_idle_ms(steady, (loop,)) == pytest.approx(1.0)
    assert progtrace.pooled_idle_ms(stalled, (loop,)) == \
        pytest.approx(1.0 + 2100 / tokens)
    assert progtrace.pooled_idle_ms(stalled, progtrace.SERVE_SYNC) == \
        pytest.approx(0.002 * 5 / tokens * 1e3)


def test_scope_readers_on_decode_runs(program):
    run, prog = program
    run.trace.modules = [("jit_decode_next(2)", 0.131, 0.145),
                         ("jit_decode_next(2)", 0.147, 0.160)]
    prog.ops[:] = [
        ("fusion.1", "jit(decode_next)/while/body/moe/dot", 0.132, 0.140),
        ("fusion.2", "jit(decode_next)/while/body/attn/dot", 0.141, 0.143),
        ("fusion.3", "jit(decode_next)/while/body/moe/dot", 0.148, 0.152)]
    assert harness.metric_module("decode_moe_device_ms").read(run) == \
        pytest.approx((0.008 + 0.004) / 2 * 1e3)
    assert harness.metric_module("decode_attn_device_ms").read(run) == \
        pytest.approx(0.002 / 2 * 1e3)


NEW = ("decode_moe_device_ms", "decode_attn_device_ms",
       "decode_loop_idle_ms", "decode_loop_stall_ms", "serve_sync_idle_ms")


@pytest.mark.parametrize("name", NEW)
def test_readers_read_nothing_without_the_programs_spans(monkeypatch,
                                                         name):
    run, _ = served_run()
    # a program without spans, scopes or named step programs
    run.trace.modules = [("jit__unknown(2)", 0.131, 0.145)]
    monkeypatch.setattr(progtrace, "of", lambda r: ProgTrace([], [
        ("fusion.1", "jit(_unknown)/while/body/dot", 0.132, 0.14)]))
    assert harness.metric_module(name).read(run) is None
    run.trace = None
    monkeypatch.setattr(progtrace, "of", lambda r: None)
    assert harness.metric_module(name).read(run) is None


def _varint(n: int) -> bytes:
    out = b""
    while n >= 0x80:
        out += bytes([n & 0x7F | 0x80])
        n >>= 7
    return out + bytes([n])


def _msg(*fields) -> bytes:
    """A protobuf message of (field number, int | bytes | str | float)."""
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        elif isinstance(value, float):  # a fixed64 field
            out += _varint(num << 3 | 1) + b"\0" * 8
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def _plane(name, ops):
    """An XPlane with stat metadata 300 ``tf_op``, 301 ``flops``, and an
    event metadata per (event name, tf_op) in ``ops``."""
    fields = [(1, 7), (2, name), (3, _msg((1, 1), (2, "XLA Ops")))]
    for sid, sname in ((300, "tf_op"), (301, "flops")):
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    for i, (ev, scope) in enumerate(ops, 1):
        meta = _msg((1, i), (2, ev), (5, _msg((1, 301), (2, 1.0))),
                    (5, _msg((1, 300), (5, scope))), (4, "display"))
        fields.append((4, _msg((1, i), (2, meta))))
    return _msg(*fields)


def test_op_scopes_read_from_the_serialized_event_metadata():
    fusion = "%fusion.1 = bf16[2]{0} fusion(x), kind=kLoop"
    space = _msg(
        (1, _plane("/host:CPU", [(fusion, "jit(host)/moe/dot:")])),
        (1, _plane("/device:TPU:0", [
            (fusion, "jit(decode_next)/while/body/moe/dot_general:"),
            ("%copy.2 = f32[2]{0} copy(y)", "jit(decode_next)/attn/x:")])))
    assert progtrace.op_scopes(space) == {
        fusion: ("jit(decode_next)/while/body/moe/dot_general",),
        "%copy.2 = f32[2]{0} copy(y)": ("jit(decode_next)/attn/x",)}
    assert progtrace.op_scopes(space, plane="/device:TPU:1") == {}


def test_a_name_two_programs_share_takes_the_scope_of_its_module():
    assert progtrace.pick_scope((), None) == ""
    assert progtrace.pick_scope(("jit(a)/moe",), None) == "jit(a)/moe"
    both = ("jit(decode_next)/moe/x", "jit(prefill_step)/attn/x")
    assert progtrace.pick_scope(both, "jit_decode_next(7)") == both[0]
    assert progtrace.pick_scope(both, "jit_prefill_step(3)") == both[1]
    assert progtrace.pick_scope(both, "jit_score_step(4)") is None
    assert progtrace.pick_scope(both, None) is None
    assert progtrace.pick_scope(("jit(f)/moe/x", "jit(f)/attn/x"),
                                "jit_f(1)") is None
    modules = [("jit_prefill_step(3)", 1.0, 2.0),
               ("jit_decode_next(7)", 3.0, 4.0)]
    starts = [s for _, s, _ in modules]
    assert [progtrace.module_at(modules, starts, t)
            for t in (0.5, 1.5, 2.5, 3.0, 4.0, 5.0)] == [
        None, "jit_prefill_step(3)", None, "jit_decode_next(7)",
        "jit_decode_next(7)", None]


def _device_space(metadata, lines):
    """An XSpace with one device plane: ``metadata`` [(id, name,
    tf_op or None)], ``lines`` {line name: [(metadata id, start s, end
    s)]}."""
    fields = [(1, 7), (2, devtrace.DEVICE_PLANE)]
    for lid, (lname, events) in enumerate(lines.items(), 1):
        evs = [(4, _msg((1, mid), (2, int(s * 1e12)),
                        (3, int((e - s) * 1e12))))
               for mid, s, e in events]
        fields.append((3, _msg((1, lid), (2, lname), (3, 0), *evs)))
    fields.append((5, _msg((1, 300), (2, _msg((1, 300), (2, "tf_op"))))))
    for mid, name, scope in metadata:
        meta = [(1, mid), (2, name)]
        if scope is not None:
            meta.append((5, _msg((1, 300), (5, scope + ":"))))
        fields.append((4, _msg((1, mid), (2, _msg(*meta)))))
    return _msg((1, _msg(*fields)))


def test_read_settles_shared_op_names_by_module_and_counts_the_rest(
        tmp_path):
    copy = "%copy.1 = f32[2]{0} copy(x)"
    fusion = "%fusion.9 = f32[2]{0} fusion(x), kind=kLoop"
    space = _device_space(
        [(1, "jit_decode_next(7)", None), (2, "jit_prefill_step(3)", None),
         (3, copy, "jit(decode_next)/moe/copy"),
         (4, copy, "jit(prefill_step)/attn/copy"),
         (5, fusion, "jit(decode_next)/moe/add"),
         (6, fusion, "jit(decode_next)/attn/add")],
        {"XLA Modules": [(2, 1.0, 1.5), (1, 2.0, 2.5)],
         "XLA Ops": [(3, 1.1, 1.2), (3, 2.1, 2.2), (5, 2.3, 2.4)]})
    out = tmp_path / "plugins" / "profile" / "run"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(space)
    got = progtrace.read(tmp_path, 0.0, 10.0)
    # the copy in the prefill run takes prefill's scope, the one in the
    # decode run decode's; the fusion's two scopes are both decode's
    assert [(n, c) for n, c, _, _ in got.ops] == [
        ("copy.1", "jit(prefill_step)/attn/copy"),
        ("copy.1", "jit(decode_next)/moe/copy"),
        ("fusion.9", "")]
    assert got.ambiguous == 1
    assert [s for _, _, s, _ in got.ops] == pytest.approx([1.1, 2.1, 2.3])


def test_read_keeps_the_programs_host_spans(tmp_path):
    """On a trace the CPU records: the tracer's spans, with their
    attributes, clipped to the window; other host events left out."""
    from repro.obs.tracing import configure_tracing, get_tracer
    configure_tracing(enabled=False)
    tracer = get_tracer()
    f = jax.jit(lambda x: x * 2)
    f(1.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            with tracer.span("engine_serve", entry="generate",
                             new_tokens=3):
                with tracer.span("engine_decode", steps=2):
                    f(2.0).block_until_ready()
            with tracer.span("component:compile.score"):
                pass
    finally:
        jax.profiler.stop_trace()
    full = progtrace.read(tmp_path, 0.0, float("inf"))
    names = [n for n, *_ in full.spans]
    assert sorted(names) == ["component:compile.score", "engine_decode",
                             "engine_serve"]
    serve = next(s for s in full.spans if s[0] == "engine_serve")
    assert serve[3]["entry"] == "generate" and serve[3]["new_tokens"] == 3
    decode = next(s for s in full.spans if s[0] == "engine_decode")
    assert serve[1] <= decode[1] and decode[2] <= serve[2]
    clipped = progtrace.read(tmp_path, decode[1], decode[2])
    assert sorted(n for n, *_ in clipped.spans) == ["engine_decode",
                                                    "engine_serve"]
    assert progtrace.span_name("engine_serve#entry=generate#") == \
        "engine_serve"
