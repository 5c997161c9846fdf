"""Serving engine + SLIMSTART Level-B behaviour tests (reduced configs)."""

import numpy as np
import pytest

from repro.configs import get_reduced
from repro.serving import ContinuousBatcher, LoadPolicy, Request, ServingEngine


@pytest.fixture(scope="module")
def moe_engine():
    cfg = get_reduced("granite-moe-1b-a400m")
    eng = ServingEngine(cfg, batch_size=1, prefill_len=8, max_len=32)
    eng.cold_start()
    return eng


def test_eager_cold_start_builds_everything():
    cfg = get_reduced("qwen2.5-32b")
    eng = ServingEngine(cfg, batch_size=1, prefill_len=8, max_len=24)
    dt = eng.cold_start()
    assert dt > 0
    rep = eng.report()
    assert rep["total_init_s"] > 0
    # every compile component materialized under the eager policy
    for row in rep["components"]:
        if row["group"] == "compile":
            assert row["ready"], row


def test_lazy_policy_defers_and_first_use_pays():
    cfg = get_reduced("whisper-large-v3")
    lazy = LoadPolicy(lazy_groups=frozenset({"compile", "frontend"}))
    eng = ServingEngine(cfg, policy=lazy, batch_size=1, prefill_len=8,
                        max_len=24)
    cold_lazy = eng.cold_start()

    eager = ServingEngine(cfg, batch_size=1, prefill_len=8, max_len=24)
    cold_eager = eager.cold_start()
    assert cold_lazy < cold_eager, \
        "deferring compilation must shrink the cold start"

    # the deferred entry still works — first use materializes it
    toks = np.random.default_rng(0).integers(0, cfg.vocab, (1, 8))
    out, lat = eng.serve("transcribe", toks, max_new_tokens=3)
    assert out.shape == (1, 3)
    assert eng.registry["compile.transcribe"].ready


def test_moe_lazy_experts_materialize_on_route(moe_engine):
    eng = moe_engine
    cfg = eng.cfg
    toks = np.random.default_rng(1).integers(0, cfg.vocab, (1, 8))
    out, _ = eng.serve("generate", toks, max_new_tokens=4)
    assert out.shape == (1, 4)
    rep = eng.report()
    assert "expert_utilization" in rep
    util = rep["expert_utilization"]
    assert abs(sum(util.values()) - 1.0) < 1e-2
    routed = [e for e, m in enumerate(eng.expert_mass) if m > 0]
    for e in routed:
        assert eng.registry[f"expert.{e}"].ready


def test_expert_weights_equal_across_hash_seeds():
    """Expert weights depend on the engine seed only: two processes with
    different ``PYTHONHASHSEED`` build the same bytes."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = Path(__file__).resolve().parents[1] / "src"
    code = (
        "import hashlib, numpy as np\n"
        "from repro.configs import get_reduced\n"
        "from repro.serving import LoadPolicy, ServingEngine\n"
        "from repro.serving.engine import _moe_groups\n"
        "eng = ServingEngine(get_reduced('granite-moe-1b-a400m'),\n"
        "    policy=LoadPolicy(lazy_groups=frozenset({'compile'})))\n"
        "eng.cold_start()\n"
        "h = hashlib.sha256()\n"
        "for moe in _moe_groups(eng._params['layers']):\n"
        "    for w in ('wi', 'wo'):\n"
        "        a = np.asarray(moe[w])\n"
        "        assert (np.abs(a).sum(axis=tuple(range(2, a.ndim))) > 0).all()\n"
        "        h.update(a.tobytes())\n"
        "print(h.hexdigest())\n")
    digests = [
        subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, check=True,
            env=dict(os.environ, PYTHONPATH=str(src), JAX_PLATFORMS="cpu",
                     PYTHONHASHSEED=seed)).stdout.strip()
        for seed in ("1", "2")]
    assert digests[0] and digests[0] == digests[1]


def test_params_init_at_one_layers_fan_in():
    """Stacked layer weights and expert weights draw at 1/sqrt(fan_in)
    of one layer's (one expert's) matrix, not of the stack or the expert
    count: random weights then keep activations at unit scale."""
    import jax
    from repro.models.model import init_params

    cfg = get_reduced("granite-moe-1b-a400m")
    p = init_params(cfg, jax.random.PRNGKey(0))["layers"]["scan"]["pos0"]
    for w, fan_in in [(p["attn"]["wq"], cfg.d_model),
                      (p["attn"]["wo"], cfg.n_heads * cfg.head_dim),
                      (p["moe"]["wi"], cfg.d_model),
                      (p["moe"]["wo"], cfg.moe.d_expert_ff)]:
        assert abs(float(np.std(np.asarray(w))) * fan_in ** 0.5 - 1) < 0.1


def test_report_feeds_policy(moe_engine):
    rep = moe_engine.report()
    pol = LoadPolicy.from_report(rep)
    # at least something is deferred and something prewarmed
    assert isinstance(pol.lazy_names, frozenset)
    # components below the 2% utilization threshold are lazy
    for row in rep["components"]:
        if row["utilization"] < 0.02 and row["init_s"] > 0:
            assert row["component"] in pol.lazy_names


def test_continuous_batcher_matches_sequential():
    """Batched continuous decoding must produce the same tokens as
    serving each request alone (greedy decoding is deterministic)."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import decode_step, init_cache, init_params, \
        prefill

    cfg = get_reduced("granite-8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_slots, cache_len = 2, 48

    def prefill_fn(tokens):
        logits, caches, _ = prefill(cfg, params, tokens,
                                    cache_len=cache_len)
        return jnp.argmax(logits, -1).astype(jnp.int32), caches

    @jax.jit
    def decode_fn(tok, pos, caches):
        logits, caches = decode_step(cfg, params, tok, pos, caches)
        return jnp.argmax(logits, -1).astype(jnp.int32)[:, None], caches

    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, (L,)) for L in (5, 7, 6)]

    # sequential reference
    ref_outs = []
    for p in prompts:
        first, caches = prefill_fn(jnp.asarray(p[None], jnp.int32))
        toks = [int(np.asarray(first)[0])]
        cur = first[:, None]
        for i in range(3):
            pos = jnp.full((1,), len(p) + i, jnp.int32)
            cur, caches = decode_fn(cur, pos, caches)
            toks.append(int(np.asarray(cur)[0, 0]))
        ref_outs.append(toks)

    batcher = ContinuousBatcher(
        prefill_fn, decode_fn, init_cache(cfg, n_slots, cache_len),
        n_slots=n_slots)
    for i, p in enumerate(prompts):
        batcher.submit(Request(rid=i, tokens=p, max_new_tokens=4))
    stats = batcher.run_until_drained()
    assert stats["finished"] == 3
    got = {r.rid: r.out_tokens for r in batcher.finished}
    for i, ref in enumerate(ref_outs):
        assert got[i] == ref, f"request {i}: {got[i]} != {ref}"
