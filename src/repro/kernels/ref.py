"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``ref_*`` mirrors its kernel's exact semantics (masking rules,
softcap placement, fp32 accumulation) with straightforward jnp code.
Kernel tests sweep shapes/dtypes and assert allclose against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ref_flash_attention(q, k, v, *, causal=True, window=None,
                        softcap=None):
    """q: (B, H, Sq, hd); k, v: (B, K, Skv, hd)."""
    B, H, Sq, hd = q.shape
    K, Skv = k.shape[1], k.shape[2]
    G = H // K
    kr = jnp.repeat(k, G, axis=1)
    vr = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * hd ** -0.5
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
    return o.astype(q.dtype)


def ref_decode_attention(q, k, v, q_pos, kv_pos, *, window=None,
                         softcap=None):
    """q: (B, K, G, hd); k, v: (B, K, S, hd); q_pos: (B,);
    kv_pos: (B, S) (-1 = empty)."""
    hd = q.shape[-1]
    s = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    valid = (kv_pos >= 0) & (kv_pos <= q_pos[:, None])
    if window is not None:
        valid &= kv_pos > (q_pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def ref_rglru_scan(a, b, h0=None):
    """h_t = a_t * h_{t-1} + b_t via jax associative scan (fp32)."""
    if h0 is None:
        h0 = jnp.zeros((a.shape[0], a.shape[2]), jnp.float32)
    a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
    b32 = b32.at[:, 0].add(a32[:, 0] * h0.astype(jnp.float32))

    def combine(c1, c2):
        a1, h1 = c1
        a2, h2 = c2
        return a1 * a2, h1 * a2 + h2

    _, h = jax.lax.associative_scan(combine, (a32, b32), axis=1)
    return h.astype(a.dtype)


def ref_moe_routed(x, wi, wo, layer, ids, weights):
    """x: (N, D); wi: (n_stack, E, D, 2F); wo: (n_stack, E, F, D);
    ids, weights: (N, k).  Each token's routed experts of layer
    ``layer``, combined by ``weights`` (casts as the capacity MoE)."""
    f32 = jnp.float32
    F = wo.shape[2]
    wi_k, wo_k = wi[layer][ids], wo[layer][ids]  # (N, k, D, 2F), (N, k, F, D)
    gu = jnp.einsum("nd,nkdf->nkf", x, wi_k,
                    preferred_element_type=f32).astype(x.dtype)
    g, u = gu[..., :F], gu[..., F:]
    h = jax.nn.gelu(g.astype(f32)).astype(x.dtype) * u
    hout = jnp.einsum("nkf,nkfd->nkd", h, wo_k, preferred_element_type=f32)
    y = jnp.einsum("nk,nkd->nd", weights.astype(f32), hout)
    return y.astype(x.dtype)
