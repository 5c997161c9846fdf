"""The float32 reference agrees with the program's own forward pass at a
tiny size on the CPU, on the weights it rebuilds from the seed."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reference import granite_moe
from repro.serving import ServingEngine
from test_run import tiny_config, tiny_spec

ARCH = "granite-moe-1b-a400m"
CAPACITY = [8.0, 0.5]   # 0.5: the prompt's tokens past capacity dropped


def served(capacity):
    cfg = tiny_config(ARCH)
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe,
                                            capacity_factor=capacity))
    spec = dict(tiny_spec(ARCH), capacity_factor=capacity)
    eng = ServingEngine(cfg, seed=7, prefill_len=16, max_len=40)
    eng.cold_start()
    return eng, spec


@pytest.mark.parametrize("capacity", CAPACITY)
def test_weights_rebuilt_bit_for_bit(capacity):
    eng, spec = served(capacity)
    ref = granite_moe.weights(spec, 7)
    ours = jax.tree.leaves(ref)
    theirs = jax.tree.leaves(dict(eng._params,
                                  layers=eng._params["layers"]["scan"]
                                  ["pos0"]))
    assert len(ours) == len(theirs)
    for a, b in zip(ours, theirs):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("capacity", CAPACITY)
def test_forward_matches_the_program(capacity):
    eng, spec = served(capacity)
    toks = np.random.default_rng(3).integers(0, spec["vocab_size"], (1, 16))
    ref = granite_moe.weights(spec, 7)
    logits, _ = eng.serve("score", toks)
    with jax.default_matmul_precision("highest"):
        want = granite_moe.forward(ref, jnp.asarray(toks[0]), spec, 16)
    np.testing.assert_allclose(logits[0], np.asarray(want), atol=1e-3)
    out, _ = eng.serve("generate", toks, max_new_tokens=12)
    seq = jnp.asarray(np.concatenate([toks[0], out[0][:-1]]))
    with jax.default_matmul_precision("highest"):
        full = np.asarray(granite_moe.forward(ref, seq, spec, 16))
    np.testing.assert_array_equal(full[15:15 + 12].argmax(-1), out[0])
