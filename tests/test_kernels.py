"""Pallas kernel validation: interpret-mode kernels vs pure-jnp oracles.

Fixed cases cover block-boundary padding, GQA grouping, windows and
softcaps across dtypes; hypothesis sweeps randomize shapes within CPU
budget.  Tolerances: fp32 1e-5 / bf16 2e-2 (matmul rounding).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # CI image without hypothesis: skip sweeps only
    st = None

    def settings(*args, **kwargs):
        return lambda fn: fn

    def given(*args, **kwargs):
        def deco(fn):
            def skipper():
                pytest.skip("hypothesis not installed")
            skipper.__name__ = fn.__name__
            return skipper
        return deco

    class _AnyStrategy:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _AnyStrategy()

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels import ref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ----------------------------------------------------------------- flash
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,K,Sq,Skv,hd,causal,window,cap",
    [
        (2, 4, 2, 64, 64, 32, True, None, None),      # GQA
        (1, 2, 2, 48, 48, 16, True, None, None),      # off-block seq
        (1, 4, 1, 40, 40, 32, True, 16, None),        # MQA + window
        (1, 2, 2, 33, 33, 16, True, None, 30.0),      # softcap + ragged
        (1, 2, 2, 16, 80, 16, False, None, None),     # bidir, Sq != Skv
    ])
def test_flash_attention_vs_ref(B, H, K, Sq, Skv, hd, causal, window, cap,
                                dtype):
    key = jax.random.PRNGKey(0)
    q = _rand(key, (B, H, Sq, hd), dtype)
    k = _rand(jax.random.fold_in(key, 1), (B, K, Skv, hd), dtype)
    v = _rand(jax.random.fold_in(key, 2), (B, K, Skv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=cap, block_q=16, block_kv=16,
                          interpret=True)
    want = ref.ref_flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@settings(max_examples=8, deadline=None)
@given(
    B=st.integers(1, 2), K=st.integers(1, 2), G=st.integers(1, 3),
    sq=st.integers(3, 40), hd=st.sampled_from([8, 16, 32]),
    causal=st.booleans(),
    window=st.sampled_from([None, 8]),
)
def test_flash_attention_hypothesis(B, K, G, sq, hd, causal, window):
    key = jax.random.PRNGKey(sq * hd + G)
    H = K * G
    q = _rand(key, (B, H, sq, hd), jnp.float32)
    k = _rand(jax.random.fold_in(key, 1), (B, K, sq, hd), jnp.float32)
    v = _rand(jax.random.fold_in(key, 2), (B, K, sq, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=16, block_kv=16, interpret=True)
    want = ref.ref_flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               **TOL[jnp.float32])


# ---------------------------------------------------------------- decode
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,K,G,S,hd,window,cap,ring",
    [
        (2, 2, 2, 64, 32, None, None, False),
        (1, 1, 4, 48, 16, None, None, False),   # MQA, ragged S
        (2, 2, 1, 40, 16, 16, None, True),      # ring buffer + window
        (1, 2, 2, 33, 16, None, 30.0, False),   # softcap
    ])
def test_decode_attention_vs_ref(B, K, G, S, hd, window, cap, ring, dtype):
    key = jax.random.PRNGKey(1)
    q = _rand(key, (B, K, G, hd), dtype)
    k = _rand(jax.random.fold_in(key, 1), (B, K, S, hd), dtype)
    v = _rand(jax.random.fold_in(key, 2), (B, K, S, hd), dtype)
    if ring:
        cur = S + 7  # wrapped ring: slot i holds position with slot == i%S
        base = jnp.arange(S)
        kv_pos = jnp.where(base <= cur % S, base + (cur // S) * S,
                           base + (cur // S - 1) * S)
        kv_pos = jnp.broadcast_to(kv_pos, (B, S))
        q_pos = jnp.full((B,), cur, jnp.int32)
    else:
        n_valid = S - 5
        kv_pos = jnp.where(jnp.arange(S) < n_valid, jnp.arange(S), -1)
        kv_pos = jnp.broadcast_to(kv_pos, (B, S))
        q_pos = jnp.full((B,), n_valid - 1, jnp.int32)
    out = decode_attention(q, k, v, q_pos, kv_pos, window=window,
                           softcap=cap, block_kv=16, interpret=True)
    want = ref.ref_decode_attention(q, k, v, q_pos, kv_pos, window=window,
                                    softcap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@settings(max_examples=8, deadline=None)
@given(B=st.integers(1, 2), K=st.integers(1, 2), G=st.integers(1, 4),
       S=st.integers(4, 50), hd=st.sampled_from([8, 16]),
       window=st.sampled_from([None, 8]))
def test_decode_attention_hypothesis(B, K, G, S, hd, window):
    key = jax.random.PRNGKey(S + hd)
    q = _rand(key, (B, K, G, hd), jnp.float32)
    k = _rand(jax.random.fold_in(key, 1), (B, K, S, hd), jnp.float32)
    v = _rand(jax.random.fold_in(key, 2), (B, K, S, hd), jnp.float32)
    kv_pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    q_pos = jnp.full((B,), S - 1, jnp.int32)
    out = decode_attention(q, k, v, q_pos, kv_pos, window=window,
                           block_kv=16, interpret=True)
    want = ref.ref_decode_attention(q, k, v, q_pos, kv_pos, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               **TOL[jnp.float32])


# ---------------------------------------------------------------- rg-lru
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,R,with_h0", [
    (2, 64, 128, False),
    (1, 40, 130, True),   # ragged channel dim
    (2, 17, 64, True),    # ragged time dim
])
def test_rglru_scan_vs_ref(B, S, R, with_h0, dtype):
    key = jax.random.PRNGKey(2)
    # decays in (0, 1) like real RG-LRU coefficients
    a = jax.nn.sigmoid(_rand(key, (B, S, R), jnp.float32)).astype(dtype)
    b = _rand(jax.random.fold_in(key, 1), (B, S, R), dtype)
    h0 = (_rand(jax.random.fold_in(key, 2), (B, R), dtype)
          if with_h0 else None)
    out = rglru_scan(a, b, h0, block_t=16, block_r=128, interpret=True)
    want = ref.ref_rglru_scan(a, b, h0)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@settings(max_examples=8, deadline=None)
@given(B=st.integers(1, 2), S=st.integers(2, 40),
       R=st.sampled_from([32, 100, 128]))
def test_rglru_hypothesis(B, S, R):
    key = jax.random.PRNGKey(S * R)
    a = jax.nn.sigmoid(_rand(key, (B, S, R), jnp.float32))
    b = _rand(jax.random.fold_in(key, 1), (B, S, R), jnp.float32)
    out = rglru_scan(a, b, block_t=16, block_r=128, interpret=True)
    want = ref.ref_rglru_scan(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------- ops layer consistency
def test_attention_op_matches_model_layer():
    """kernels.ops must agree with the model's XLA attention path."""
    from repro.models import layers as L
    from repro.kernels import ops
    key = jax.random.PRNGKey(3)
    B, S, K, G, hd = 2, 32, 2, 2, 16
    q = _rand(key, (B, S, K, G, hd), jnp.float32)
    k = _rand(jax.random.fold_in(key, 1), (B, S, K, hd), jnp.float32)
    v = _rand(jax.random.fold_in(key, 2), (B, S, K, hd), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    for window, cap in [(None, None), (8, None), (None, 30.0)]:
        xla = L.attention(q, k, v, q_positions=pos, kv_positions=pos,
                          causal=True, window=window, softcap_val=cap)
        pallas = ops.attention_op(q, k, v, causal=True, window=window,
                                  softcap=cap)
        np.testing.assert_allclose(np.asarray(pallas), np.asarray(xla),
                                   rtol=2e-3, atol=2e-3)


# ------------------------------------------------------- routed-expert MoE
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E", [8, 32])
@pytest.mark.parametrize("k", [1, 2, 8])
@pytest.mark.parametrize("n_stack,layer", [(1, 0), (3, 0), (3, 2)])
def test_moe_routed_decode_vs_ref(n_stack, layer, k, E, dtype):
    from repro.kernels.moe_decode import moe_routed_decode
    key = jax.random.PRNGKey(4)
    N, D, F = 2, 64, 32
    x = _rand(key, (N, D), dtype)
    wi = (_rand(jax.random.fold_in(key, 1), (n_stack, E, D, 2 * F),
                jnp.float32) * D ** -0.5).astype(dtype)
    wo = (_rand(jax.random.fold_in(key, 2), (n_stack, E, F, D),
                jnp.float32) * F ** -0.5).astype(dtype)
    ids = jnp.stack([jax.random.permutation(jax.random.fold_in(key, 3 + n),
                                            E)[:k] for n in range(N)])
    w = jax.nn.softmax(_rand(jax.random.fold_in(key, 9), (N, k),
                             jnp.float32))
    out = moe_routed_decode(x, wi, wo, jnp.int32(layer), ids, w,
                            interpret=True)
    want = ref.ref_moe_routed(x, wi, wo, layer, ids, w)
    assert out.shape == (N, D) and out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def _force_capacity(monkeypatch):
    from repro.models import layers as L
    monkeypatch.setattr(L, "moe_takes_routed_path", lambda *a: False)


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("B", [1, 2])
@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "olmoe-1b-7b"])
def test_moe_routed_path_matches_capacity_path(monkeypatch, arch, B,
                                               stacked):
    """At decode shapes the reduced configs (top-2 of 8, capacity 8.0)
    take the routed path; it gives the capacity path's result, from a
    per-layer weight or from the whole stack indexed by the layer."""
    from repro.configs import get_reduced
    from repro.models import layers as L
    from repro.models.model import _stack_specs
    cfg = get_reduced(arch)
    key = jax.random.PRNGKey(5)
    n = 3 if stacked else 1
    specs = _stack_specs(L.moe_template(cfg), n)
    p = {name: s.std() * _rand(jax.random.fold_in(key, i), s.shape,
                               jnp.float32)
         for i, (name, s) in enumerate(sorted(specs.items()))}
    x = _rand(jax.random.fold_in(key, 7), (B, 1, cfg.d_model), jnp.float32)
    layer = 2 if stacked else None
    flat = {k: v[n - 1] for k, v in p.items()}
    blk = {**flat, "wi": p["wi"], "wo": p["wo"]} if stacked else flat

    with L.moe_paths() as seen:
        y, aux = L.moe_apply(blk, cfg, x, layer=layer)
    assert seen == {"routed"}
    _force_capacity(monkeypatch)
    with L.moe_paths() as seen:
        y_cap, aux_cap = L.moe_apply(flat, cfg, x)
    assert seen == {"capacity"}
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_cap),
                               **TOL[jnp.float32])
    for name in aux:
        np.testing.assert_array_equal(np.asarray(aux[name]),
                                      np.asarray(aux_cap[name]))


@pytest.fixture(scope="module")
def moe_engine_pair():
    """Two engines of the reduced granite-moe on the same weights, one
    compiled with the routed decode path (key True) and one with it
    forced off (key False)."""
    from repro.configs import get_reduced
    from repro.serving import ServingEngine
    pair = {}
    for routed in (True, False):
        with pytest.MonkeyPatch.context() as mp:
            if not routed:
                _force_capacity(mp)
            eng = ServingEngine(get_reduced("granite-moe-1b-a400m"),
                                seed=11, prefill_len=16, max_len=40)
            eng.cold_start()
        pair[routed] = eng
    return pair


@pytest.mark.parametrize("prompt", [0, 1, 2])
def test_engine_greedy_tokens_do_not_depend_on_the_moe_path(
        monkeypatch, moe_engine_pair, prompt):
    """The same greedy tokens with the routed decode path on and forced
    off, and the decode step's logits equal to f32 rounding."""
    from repro.models.model import decode_step, prefill
    on, off = moe_engine_pair[True], moe_engine_pair[False]
    assert on.moe_paths["generate"] == {"routed", "capacity"}
    assert off.moe_paths["generate"] == {"capacity"}
    cfg, params = on.cfg, on._params
    toks = np.random.default_rng(prompt).integers(0, cfg.vocab, (1, 16))
    got = on.serve("generate", toks, max_new_tokens=10)[0]
    np.testing.assert_array_equal(
        got, off.serve("generate", toks, max_new_tokens=10)[0])

    _, caches, _ = jax.jit(functools.partial(prefill, cfg, cache_len=40))(
        params, jnp.asarray(toks))
    tok, pos = jnp.asarray(got[:, :1]), jnp.asarray([16])
    routed = jax.jit(functools.partial(decode_step, cfg))(
        params, tok, pos, caches)[0]
    _force_capacity(monkeypatch)
    capacity = jax.jit(functools.partial(decode_step, cfg))(
        params, tok, pos, caches)[0]
    np.testing.assert_allclose(np.asarray(routed), np.asarray(capacity),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("program,batch,capacity_factor,mesh,path", [
    ("decode", 1, None, False, "routed"),
    ("decode", 1, 1.25, False, "routed"),
    ("prefill", 1, None, False, "capacity"),
    ("score", 1, None, False, "capacity"),
    ("decode", 2, 1.25, False, "capacity"),  # cap 1 < group 2
    ("decode", 1, None, True, "capacity"),   # a mesh is active
])
def test_moe_path_rule(program, batch, capacity_factor, mesh, path):
    """Only a step that routes few tokens with no drop possible and no
    mesh reads the routed experts alone; prefill, score, batched decode
    past capacity and a sharded program keep the capacity dispatch."""
    import dataclasses
    from jax.sharding import Mesh
    from repro.configs import get_reduced
    from repro.models import layers as L
    from repro.serving import ServingEngine
    cfg = get_reduced("granite-moe-1b-a400m")
    if capacity_factor is not None:
        cfg = cfg.with_(moe=dataclasses.replace(
            cfg.moe, capacity_factor=capacity_factor))
    eng = ServingEngine(cfg, batch_size=batch, prefill_len=16, max_len=24)
    entry = "score" if program == "score" else "generate"
    fn, args = eng.entry_programs(entry)[program]
    with L.moe_paths() as seen:
        if mesh:
            with Mesh(np.array(jax.devices()[:1]), ("data",)):
                fn.lower(*args)
        else:
            fn.lower(*args)
    assert seen == {path}
