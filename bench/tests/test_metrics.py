"""Each metric's reader gives known numbers on a small recorded run."""

import pytest

import devtrace
import harness
from reference import granite_moe
from run import Run

SPEC = {"reference": "granite_moe", "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 32, "num_hidden_layers": 2,
        "vocab_size": 256, "dtype": "bfloat16", "num_local_experts": 8,
        "num_experts_per_tok": 2, "engine": {"prefill_len": 16}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def rec(i, path, entry, due, start, end, new_tokens):
    return {"i": i, "tenant": "tenant0", "path": path, "entry": entry,
            "due": due, "start": start, "end": end,
            "new_tokens": new_tokens}


def recorded():
    """Four dispatches: a cold generate of 4 tokens, a warm generate of
    4, a warm score, a warm generate of 6; each step program's runs on
    the device (prefill ``jit__unknown(1)``, decode ``jit__unknown(2)``,
    score ``jit__unknown(3)``, and a tiny per-token ``jit_full(9)``)."""
    w = harness.Window()
    w.records = [
        rec(0, "cold", "generate", 0.0, 0.0, 1.0, 4),
        rec(1, "warm", "generate", 0.5, 1.0, 1.2, 4),   # waited 0.5
        rec(2, "warm", "score", 1.5, 1.5, 1.6, 0),
        rec(3, "warm", "generate", 2.0, 2.0, 2.3, 6),
    ]
    w.seconds, w.traced_s = 2.3, 2.5
    mods = [("jit__unknown(1)", 10.30, 10.35)]
    mods += [("jit__unknown(2)", 10.40 + 0.03 * i, 10.42 + 0.03 * i)
             for i in range(3)]
    mods += [("jit__unknown(1)", 11.00, 11.05)]
    for i in range(3):
        a = 11.10 + 0.03 * i
        mods += [("jit__unknown(2)", a, a + 0.02),
                 ("jit_full(9)", a + 0.02, a + 0.021)]
    mods += [("jit__unknown(3)", 11.50, 11.58),
             ("jit__unknown(1)", 12.00, 12.04)]
    mods += [("jit__unknown(2)", 12.05 + 0.03 * i, 12.07 + 0.03 * i)
             for i in range(5)]
    tr = devtrace.Trace(
        t0=10.0, t1=12.5,
        ops=[("fusion.%d" % (i % 2), a, b)
             for i, (_, a, b) in enumerate(mods)],
        modules=mods,
        marks=[("bench.cold_start", 10.0, 10.5), ("bench.serve", 11.0, 11.2),
               ("bench.wait", 11.2, 11.45), ("bench.serve", 11.5, 11.6),
               ("bench.serve", 12.0, 12.3)])
    return Run(SPEC, PEAKS, {"setup_s": 20.5, "import_s": 3.25,
                             "first_coldstart_s": 7.5}, w, 15e9, tr)


CASES = {
    # latencies 1.0, 0.7, 0.1, 0.3 s: linear p95 at rank 2.85 of 0..3
    "request_p95_ms": 955.0,
    "warm_ms_per_token": (0.2 + 0.3) / 10 * 1e3,
    "peak_hbm_gb": 15.0,
    "setup_s": 20.5,
    "setup_import_s": 3.25,
    "setup_first_coldstart_s": 7.5,
    "pool_wait_ms": 0.5 / 4 * 1e3,
    "prefill_device_ms": (0.05 + 0.05 + 0.04) / 3 * 1e3,
    "decode_device_ms": 20.0,
    "device_idle_share": 1 - (0.113 + 0.08 + 0.14) / 0.6,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_on_recorded_run(name):
    got = harness.metric_module(name).read(recorded())
    assert got == pytest.approx(CASES[name])


def test_decode_mfu():
    run = recorded()
    # decode steps of the generate requests started in the traced part:
    # contexts 16..18, 16..18, 16..20 -> mean 17.333...
    ctx = [16 + j for n in (4, 4, 6) for j in range(n - 1)]
    flops, nbytes = granite_moe.decode_step(SPEC, sum(ctx) / len(ctx))
    want = max(flops / 1e12, nbytes / 1e9) / 0.02
    got = harness.metric_module("decode_mfu").read(run)
    assert got == pytest.approx(want)
    assert 0 < got


def test_decode_step_counts_routed_experts_only():
    f0, b0 = granite_moe.decode_step(SPEC, 0)
    D, H, K, F, L, V, E, k = 64, 4, 2, 32, 2, 256, 8, 2
    hd = D // H
    matmul = L * (D * H * hd + 2 * D * K * hd + H * hd * D
                  + D * E + k * 3 * D * F) + V * D
    assert f0 == 2 * matmul + L * 2 * 2 * H * hd
    # bf16 weights, norms and one embedding row; this token's K and V
    assert b0 == 2 * (matmul + L * 2 * D + 2 * D) + L * 2 * K * hd * 2
    f1, b1 = granite_moe.decode_step(SPEC, 10)
    assert b1 - b0 == L * 2 * 10 * K * hd * 2


def test_readers_without_a_trace_read_nothing():
    run = recorded()
    run.trace = None
    for name in ("prefill_device_ms", "decode_device_ms",
                 "device_idle_share", "decode_mfu"):
        assert harness.metric_module(name).read(run) is None


def test_step_programs_told_apart_by_run_counts():
    progs = devtrace.step_programs(recorded().trace, recorded().window.records)
    assert len(progs["prefill"]) == 3 and len(progs["decode"]) == 11


def test_breakdown_tags_idle_gaps():
    bd = devtrace.breakdown(recorded().trace)
    assert sorted(n for n, _ in bd["device_ops"]) == ["fusion.0", "fusion.1"]
    gaps = dict((round(v, 3), n) for n, v in bd["idle_gaps"])
    assert gaps[0.3] == "cold_start"      # 10.0-10.3
    assert gaps[0.319] == "wait"          # 11.181-11.5
    assert gaps[0.42] == "host"           # 11.58-12.0


def test_op_names_are_the_instruction_names():
    assert devtrace.op_name("%fusion.12 = bf16[2]{0} fusion(x)") == \
        "fusion.12"
