"""Plain float32 pieces of the Granite decoder, shared by the reference
forwards in this directory, and the served model's weights rebuilt from
its seed.

Nothing here imports the program under test.  The weights are rebuilt
from the recipe the served engine uses (one normal draw per parameter
leaf, in the order a sorted-key pytree flattens, scaled by 1/sqrt(fan
in) and stored in bfloat16; MoE experts drawn per expert from keys that
fold in the expert, the layer group and the weight index), so the
reference computes on the same bfloat16 values without taking anything
the program made.

Every matmul of a forward goes through ``mm``/``ein`` at
``Precision.HIGHEST``: on a TPU a float32 matmul otherwise runs in
bfloat16 passes.  ``quant`` fake-quantizes both operands of each linear
layer: the control of the correctness check runs the same forward with
it set, in a precision below the served one.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

Quant = Optional[Callable[[jax.Array, int], jax.Array]]


# ----------------------------------------------------------- numerics
def mm(x, w, quant: Quant = None):
    """x (..., d) @ w (d, f) in float32; ``quant(a, axis)`` rounds each
    operand to a lower precision, scaled along the contracted axis."""
    if quant is not None:
        x, w = quant(x, -1), quant(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def ein(spec, *ops):
    return jnp.einsum(spec, *ops, precision=HIGHEST)


def rms_norm(x, scale, eps):
    """Scale stored as an offset from 1 (zeros at init)."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x * x * x)))


def rope(x, theta):
    """Half-rotation rotary embedding.  x: (S, heads, hd) at positions
    0..S-1."""
    S, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, m, quant: Quant = None):
    """Causal grouped-query attention over positions 0..S-1.  Query
    head h reads key/value head h // (heads / kv_heads)."""
    S = x.shape[0]
    H, K = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // H
    q = mm(x, p["wq"], quant).reshape(S, H, hd)
    k = mm(x, p["wk"], quant).reshape(S, K, hd)
    v = mm(x, p["wv"], quant).reshape(S, K, hd)
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    s = ein("shd,thd->hst", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = ein("hst,thd->shd", jax.nn.softmax(s, axis=-1), v)
    return mm(o.reshape(S, H * hd), p["wo"], quant)


def decoder(params, tokens, m, mixer, quant: Quant = None):
    """Pre-norm residual decoder with tied embeddings.  ``mixer(p, h)``
    is the feed-forward half of one layer.  Returns (S, vocab) float32
    logits for every position."""
    eps = m["rms_norm_eps"]

    def layer(x, p):
        x = x + attention(p["attn"], rms_norm(x, p["ln1"], eps), m, quant)
        x = x + mixer(p, rms_norm(x, p["ln2"], eps))
        return x, None

    x = params["embed"][tokens]
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rms_norm(x, params["final_norm"], eps)
    return mm(x, params["embed"].T, quant)


# ------------------------------------------------------------ weights
REPLACED = "replaced"


def normal_leaf(shape, std=None):
    """A parameter drawn from N(0, std^2); std None means zeros, and
    ``REPLACED`` a draw the served engine discards (it still takes its
    key)."""
    return {"shape": tuple(shape), "std": std}


def _flatten(tree, prefix=()):
    """Leaves of a nested dict in sorted-key order, as jax flattens it."""
    if "shape" in tree and "std" in tree:
        return [(prefix, tree)]
    out = []
    for k in sorted(tree):
        out += _flatten(tree[k], prefix + (k,))
    return out


def _set(tree, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def seeded_weights(template, seed, stored=jnp.bfloat16):
    """The parameters one draw per leaf gives, rounded to ``stored`` and
    returned in float32.  ``template`` nests dicts down to
    ``normal_leaf``s; fan-in scales are the leaves' ``std``.

    Run eagerly, op by op, as the served engine draws them: jitted, the
    draw and scale can fuse and round a last bit differently."""
    leaves = _flatten(template)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out: dict = {}
    for (path, leaf), k in zip(leaves, keys):
        if leaf["std"] == REPLACED:
            continue
        if leaf["std"] is None:
            v = jnp.zeros(leaf["shape"], F32)
        else:
            v = (jax.random.normal(k, leaf["shape"], F32) * leaf["std"]
                 ).astype(stored).astype(F32)
        _set(out, path, v)
    return out


def seeded_experts(seed, n_experts, shapes, stored=jnp.bfloat16):
    """Expert weights, each drawn on its own: expert e's slice of weight
    i in layer group 0 comes from fold_in(fold_in(fold_in(key(seed),
    1000 + e), 0), i), scaled by 1/sqrt(its fan-in).  ``shapes`` maps
    the weight names, in order, to their (layers, fan_in, fan_out).
    Returns name -> (layers, experts, fan_in, fan_out) float32."""
    root = jax.random.PRNGKey(seed)
    out = {}
    for i, (name, shape) in enumerate(shapes):
        per = []
        for e in range(n_experts):
            k = jax.random.fold_in(jax.random.fold_in(
                jax.random.fold_in(root, 1000 + e), 0), i)
            sub = jax.random.normal(k, shape, F32) / np.sqrt(shape[1])
            per.append(sub.astype(stored))
        out[name] = jnp.stack(per, axis=1).astype(F32)
        del per
    return out


# ------------------------------------------------------ lower precision
def fake_quant_fp8(a, axis):
    """float8 e4m3 with one scale per slice along ``axis`` (the slice's
    largest magnitude maps to 448, the format's largest)."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.maximum(amax / 448.0, 1e-12)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


QUANT = {"fp8": fake_quant_fp8}
