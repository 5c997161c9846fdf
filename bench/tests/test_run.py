"""``run.py`` end to end on the CPU at a tiny size, with the look for a
chip stepped over: a sound run is correct, a run whose step programs
are broken underneath is not, the control reads wider than the
program, and without a TPU the command prints no result."""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import harness
import run
from repro.configs import get_reduced

ROOT = harness.ROOT


# With random weights and a tied head, most decoded tokens repeat the
# token before them (that token's own embedding wins).  At 16 layers
# enough of them depend on the context for a decode step that ignores
# its cache to serve other tokens.
LAYERS = 16
ARCH = "granite-moe-1b-a400m"


def tiny_config(arch):
    return get_reduced(arch).with_(n_layers=LAYERS)


def tiny_spec(arch=ARCH):
    cfg = tiny_config(arch)
    return {
        "arch": arch, "program_overrides": {}, "reference": "granite_moe",
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "num_hidden_layers": cfg.n_layers, "vocab_size": cfg.vocab,
        "rope_theta": cfg.rope_theta, "tie_word_embeddings": True,
        "dtype": cfg.dtype, "rms_norm_eps": cfg.norm_eps,
        "intermediate_size": cfg.moe.d_expert_ff,
        "num_local_experts": cfg.moe.n_experts,
        "num_experts_per_tok": cfg.moe.top_k,
        "capacity_factor": cfg.moe.capacity_factor,
        "moe_group": cfg.moe_group,
        "engine": {"batch_size": 1, "prefill_len": 16, "max_len": 40},
        "check": {"logit_gap_limit": 0.5, "score_err_quantile": 0.5,
                  "score_err_limit": 1e-3, "control": "fp8"},
    }


def tiny_mix(resident=True):
    """The cell's mix at a tiny size: one resident tenant, or two that
    take turns, so that engines cold-start inside the window."""
    mix = {"kind": "tenant_churn", "rate": 12.0, "prompt_len": 16,
           "new_tokens": {"median": 6, "sigma": 0.7, "min": 2, "max": 12},
           "score_share": 0.05, "tenants": 1, "switch_mean_s": 0,
           "zipf_s": 1.0, "profile_requests": 3, "timeline_seed": 5,
           "resident": True}
    if not resident:
        mix.update(tenants=2, switch_mean_s=0.6, resident=False)
    return mix


class CpuChip:
    """The CPU device, with the memory reading a chip gives (the CPU
    gives none)."""

    def __init__(self, dev):
        self.dev = dev

    def __getattr__(self, name):
        return getattr(self.dev, name)

    def memory_stats(self):
        return {"peak_bytes_in_use": 1 << 20}


def tiny_cell(monkeypatch, name="moe-warm", resident=True):
    """Run cell ``name``'s harness on a tiny model on the CPU."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.Cell(name, 1, tiny_spec(), tiny_mix(resident), bench)
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    monkeypatch.setattr(harness, "chip_or_exit",
                        lambda chips, peaks: CpuChip(jax.devices()[0]))
    cpu = jax.devices()[0].device_kind
    peaks = harness.load_peaks()
    monkeypatch.setattr(harness, "load_peaks",
                        lambda: {**peaks, cpu: peaks["TPU v5 lite"]})
    monkeypatch.setattr(harness, "program_config", lambda spec: harness.
                        matching(tiny_config(spec["arch"]), spec))
    monkeypatch.setattr(harness, "pin_compile_cache", lambda: "off")
    return cell


def result_of(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("resident", [True, False])
def test_sound_run_is_correct(capsys, monkeypatch, resident):
    tiny_cell(monkeypatch, resident=resident)
    assert run.main(["--workload", "moe-warm", "--seed", "2200000001",
                     "--seconds", "1.5"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    res, info = json.loads(out[-1]), json.loads(out[-2])
    assert res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] >= 10
    assert set(res["metrics"]) == {"warm_ms_per_token", "peak_hbm_gb",
                                   "setup_s"}
    assert list(res)[-1] == "compared"
    assert ("cold" in info["paths"]) != resident


def altered_token(decode):
    def step(cfg, params, token, pos, caches):
        nxt, caches = decode(cfg, params, token, pos, caches)
        return (nxt + 1) % cfg.vocab, caches
    return step


def unchanged_state(decode):
    def step(cfg, params, token, pos, caches):
        nxt, _ = decode(cfg, params, token, pos, caches)
        return nxt, caches
    return step


def altered_answer(score):
    def step(cfg, params, tokens):
        return jnp.roll(score(cfg, params, tokens), 1, axis=-1)
    return step


FAULTS = {"altered_token": ("decode_next", altered_token, "logit_gap"),
          "unchanged_state": ("decode_next", unchanged_state, "logit_gap"),
          "altered_answer": ("score_step", altered_answer, "score_err")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_step_is_not_correct(capsys, monkeypatch, fault):
    """A token altered where the decode step produces it; a decode step
    that hands back the cache it was given; ``score`` logits altered
    where the score step produces them."""
    from repro.serving import engine
    tiny_cell(monkeypatch)
    where, broken, number = FAULTS[fault]
    monkeypatch.setattr(engine, where, broken(getattr(engine, where)))
    assert run.main(["--workload", "moe-warm", "--seed", "7",
                     "--seconds", "1.5"]) == 0
    res = result_of(capsys)
    assert res["correct"] is False
    assert res["compared"][number]["value"] > \
        res["compared"][number]["limit"]


def test_control_reads_wider_than_the_program():
    """At the served sizes this comparison is ``calibrate.py control``;
    here the model is float32 and tiny, and the control the same fp8.
    Put in the program's place, the control is not correct."""
    from repro.serving import ServingEngine
    spec = tiny_spec()
    cfg = tiny_config(ARCH)
    spec["engine"]["max_len"] = 64
    eng = ServingEngine(cfg, seed=11, prefill_len=16, max_len=64)
    eng.cold_start()
    rng = np.random.default_rng(0)
    picked = []
    for i in range(8):
        prompt = rng.integers(0, cfg.vocab, 16).astype(np.int32)
        out, _ = eng.serve("generate", prompt[None], max_new_tokens=40)
        picked.append({"i": i, "tenant": "t", "entry": "generate",
                       "prompt": prompt, "out": out, "new_tokens": 40})
    logits, _ = eng.serve("score", prompt[None])
    picked.append({"i": 8, "tenant": "t", "entry": "score",
                   "prompt": prompt, "out": logits, "new_tokens": 0})
    reading = check.read(spec, {"t": 11}, picked, control="fp8")
    assert reading.widest_gap < 1e-3
    assert check.score_err(reading.errs, 0.5) < 1e-5
    assert reading.control_gap > 0.1
    assert check.score_err(reading.control_errs, 0.5) > 1e-2
    assert reading.altered_gap > reading.control_gap
    assert check.is_correct(check.verdict(spec, reading, 0), reading)
    ctl = check.control_in_place(reading)
    assert not check.is_correct(check.verdict(spec, ctl, 0), ctl)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "moe-warm", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_refuses_with_the_benchmark_files_alone(tmp_path):
    """A checkout that holds only ``BENCHMARK.json`` and ``bench/`` has
    no program to serve: the command prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "moe-warm",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "does not import" in proc.stderr
