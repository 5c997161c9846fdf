"""One module per model family (``reference/<family>.py``) reads a
configuration file's published keys; the harness only calls it.  The
Granite file passes the size check and fails it with any one size
changed, the Granite decode count is what it was when it lived in
``work.py``, and a family with other keys (DeepSeek's names) is a
module of its own, registered here, with nothing under ``bench/``
edited."""

import json
import sys
import types

import pytest

import harness
from reference import granite_moe
from repro.configs import get_config, get_reduced
from test_metrics import recorded
from work import least_time

GRANITE = json.loads(
    (harness.BENCH / "configs" / "granite-moe-1b-a400m.json").read_text())

# each published size of the Granite file, and a value the program's
# granite-moe-1b-a400m does not have
CHANGED = {
    "hidden_size": 1536,
    "intermediate_size": 1024,
    "num_attention_heads": 32,
    "num_key_value_heads": 16,
    "num_hidden_layers": 32,
    "num_local_experts": 40,
    "num_experts_per_tok": 6,
    "vocab_size": 49152,
    "rope_theta": 500000.0,
    "tie_word_embeddings": False,
    "dtype": "float32",
    "rms_norm_eps": 1e-05,
    "capacity_factor": 2.0,
    "moe_group": 1024,
}

# (FLOPs, bytes) of one decoded token of the Granite file at contexts
# 0, 256 and 300.5, as work.decode_step counted them before the move
DECODE = {
    0: (857315328.0, 857368576.0),
    256: (882481152.0, 869951488.0),
    300.5: (886855680.0, 872138752.0),
}


def test_granite_file_matches_the_program():
    cfg = get_config(GRANITE["arch"])
    assert harness.matching(cfg, GRANITE) is cfg


@pytest.mark.parametrize("key", sorted(CHANGED))
def test_any_changed_size_exits(key):
    assert GRANITE[key] != CHANGED[key]
    with pytest.raises(SystemExit, match="is not the configuration"):
        harness.matching(get_config(GRANITE["arch"]),
                         dict(GRANITE, **{key: CHANGED[key]}))


@pytest.mark.parametrize("context", sorted(DECODE))
def test_granite_decode_step_is_unchanged(context):
    assert granite_moe.decode_step(GRANITE, context) == DECODE[context]


def standin_family():
    """A family whose file uses DeepSeek-V2's key names: the routed
    experts' count and width under ``n_routed_experts`` and
    ``moe_intermediate_size``, and ``intermediate_size`` for the dense
    MLP."""
    mod = types.ModuleType("reference.standin")

    def program_sizes(m):
        return {
            "d_model": m["hidden_size"],
            "n_heads": m["num_attention_heads"],
            "n_kv_heads": m["num_key_value_heads"],
            "n_layers": m["num_hidden_layers"],
            "d_ff": m["intermediate_size"],
            "vocab": m["vocab_size"],
            "moe.n_experts": m["n_routed_experts"],
            "moe.top_k": m["num_experts_per_tok"],
            "moe.d_expert_ff": m["moe_intermediate_size"],
        }

    def decode_step(m, context):
        return float(m["hidden_size"] * 1e6), float(context * 1e6 + 1e7)

    mod.program_sizes = program_sizes
    mod.decode_step = decode_step
    return mod


@pytest.fixture
def standin(monkeypatch):
    mod = standin_family()
    monkeypatch.setitem(sys.modules, "reference.standin", mod)
    before = sorted(p.name for p in (harness.BENCH / "reference").iterdir())
    yield mod
    assert sorted(p.name for p in (harness.BENCH / "reference").iterdir()) \
        == before


def standin_spec(cfg):
    return {"arch": cfg.name, "reference": "standin",
            "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "num_hidden_layers": cfg.n_layers,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
            "n_routed_experts": cfg.moe.n_experts,
            "num_experts_per_tok": cfg.moe.top_k,
            "moe_intermediate_size": cfg.moe.d_expert_ff}


def test_standin_family_passes_the_size_check(standin):
    cfg = get_reduced("olmoe-1b-7b")
    spec = standin_spec(cfg)
    assert harness.matching(cfg, spec) is cfg
    with pytest.raises(KeyError):    # Granite's module cannot read it
        granite_moe.program_sizes(spec)


@pytest.mark.parametrize("wrong", ["n_routed_experts",
                                   "moe_intermediate_size", "no_experts"])
def test_standin_family_refuses_another_program(standin, wrong):
    cfg = get_reduced("olmoe-1b-7b")
    spec = standin_spec(cfg)
    if wrong == "no_experts":   # a path through ``moe`` on a dense model
        cfg = cfg.with_(moe=None)
    else:
        spec[wrong] += 1
    with pytest.raises(SystemExit, match="is not the configuration"):
        harness.matching(cfg, spec)


def test_decode_mfu_reads_the_family_count(standin):
    run = recorded()
    run.spec = dict(run.spec, reference="standin")
    ctx = [16 + j for n in (4, 4, 6) for j in range(n - 1)]
    flops, nbytes = standin.decode_step(run.spec, sum(ctx) / len(ctx))
    want = least_time(flops, nbytes, run.peaks) / 0.02
    assert harness.metric_module("decode_mfu").read(run) == \
        pytest.approx(want)
