"""Building blocks for the unified architecture zoo.

Every block comes in three parts sharing one source of truth:

* ``*_template(cfg)`` — a flat dict ``name -> ParamSpec(shape, axes, init)``
  describing parameters.  ``axes`` are *logical* axis names resolved to
  mesh axes by ``repro.distributed.sharding`` (single source of truth for
  both initialization and partitioning).
* ``*_apply(params, cfg, x, ...)`` — full-sequence forward (train/prefill).
* ``*_decode(params, cfg, x, cache, ...)`` — single-token forward with a
  recurrent/KV state, returning ``(y, new_cache)``.

Numerics policy: parameters and activations are ``cfg.jdtype`` (bf16 by
default); every matmul accumulates in fp32 (``preferred_element_type``);
norms / softmax / recurrences run in fp32 and cast back.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ArchConfig
from repro.models.partition import _current_mesh, constrain

f32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]  # logical axes, len == ndim
    init: str = "normal"  # "normal" | "zeros" | "ones"
    scale: Optional[float] = None  # None => 1/sqrt(fan_in)

    def std(self) -> float:
        """Standard deviation of a "normal" init: ``scale``, else
        1/sqrt(fan_in) with the fan-in on the leading axis."""
        if self.scale is not None:
            return self.scale
        fan_in = self.shape[0] if len(self.shape) > 1 else self.shape[-1]
        return 1.0 / math.sqrt(max(fan_in, 1))

    def initializer(self, key, dtype):
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        return (jax.random.normal(key, self.shape, f32) * self.std()
                ).astype(dtype)


# --------------------------------------------------------------------------
# primitives
# --------------------------------------------------------------------------

def dot(x, w):
    """Matmul with fp32 accumulation, output in x.dtype."""
    return jnp.einsum("...d,df->...f", x, w,
                      preferred_element_type=f32).astype(x.dtype)


def rms_norm(x, scale, eps):
    x32 = x.astype(f32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(f32))).astype(x.dtype)


def softcap(x, cap):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def rope(x, positions, theta):
    """Rotary embedding, half-rotation convention (llama/gemma).

    x: (B, S, ..., head_dim) with any number of middle (head) dims;
    positions: (B, S) absolute positions.
    """
    head_dim = x.shape[-1]
    half = head_dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=f32) / half)
    ang = positions[..., None].astype(f32) * freq  # (B, S, half)
    extra = x.ndim - positions.ndim - 1  # head dims to broadcast over
    ang = ang.reshape(ang.shape[:-1] + (1,) * extra + (half,))
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half].astype(f32), x[..., half:].astype(f32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _attn_scores(q, k, scale, cap):
    # q: (B, S, K, G, hd), k: (B, T, K, hd) -> (B, K, G, S, T)
    s = jnp.einsum("bskgd,btkd->bkgst", q, k, preferred_element_type=f32)
    return softcap(s * scale, cap)


def _attn_out(p, v):
    # p: (B, K, G, S, T) fp32, v: (B, T, K, hd)
    return jnp.einsum("bkgst,btkd->bskgd", p.astype(v.dtype), v,
                      preferred_element_type=f32)


def attention(q, k, v, *, q_positions, kv_positions, causal=True,
              window=None, softcap_val=None, chunk_q=0, chunk_kv=0):
    """Masked multi-query attention (GQA layout).

    q: (B, S, K, G, hd); k, v: (B, T, K, hd).
    q_positions: (B, S) absolute positions of queries.
    kv_positions: (B, T) absolute positions of keys (-1 = invalid slot).
    window: if set, keys with q_pos - k_pos >= window are masked (local).
    chunk_q/chunk_kv: if >0 use the memory-efficient online-softmax path.
    """
    if chunk_q and chunk_kv and q.shape[1] > 1:
        return _chunked_attention(q, k, v, q_positions=q_positions,
                                  kv_positions=kv_positions, causal=causal,
                                  window=window, softcap_val=softcap_val,
                                  chunk_q=chunk_q, chunk_kv=chunk_kv)
    scale = q.shape[-1] ** -0.5
    s = _attn_scores(q, k, scale, softcap_val)  # (B,K,G,S,T) fp32
    mask = _attn_mask(q_positions, kv_positions, causal, window)
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = _attn_out(p, v)
    return o.astype(q.dtype)


def _attn_mask(q_pos, kv_pos, causal, window):
    # (B, S, T) boolean validity
    qp = q_pos[:, :, None].astype(jnp.int32)
    kp = kv_pos[:, None, :].astype(jnp.int32)
    mask = kp >= 0
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > qp - window
    return mask


def _chunked_attention(q, k, v, *, q_positions, kv_positions, causal,
                       window, softcap_val, chunk_q, chunk_kv):
    """Online-softmax attention, O(chunk_q * chunk_kv) score memory.

    Mirrors the Pallas flash kernel (kernels/flash_attention.py); this is
    the XLA-path equivalent used for long-sequence prefill.
    """
    B, S, K, G, hd = q.shape
    T = k.shape[1]
    scale = hd ** -0.5
    cq = min(chunk_q, S)
    ckv = min(chunk_kv, T)
    nq, nkv = -(-S // cq), -(-T // ckv)
    pad_q, pad_kv = nq * cq - S, nkv * ckv - T

    qp = jnp.pad(q_positions, ((0, 0), (0, pad_q)))
    kvp = jnp.pad(kv_positions, ((0, 0), (0, pad_kv)), constant_values=-1)
    q_ = jnp.pad(q, ((0, 0), (0, pad_q)) + ((0, 0),) * 3)
    k_ = jnp.pad(k, ((0, 0), (0, pad_kv)) + ((0, 0),) * 2)
    v_ = jnp.pad(v, ((0, 0), (0, pad_kv)) + ((0, 0),) * 2)

    q_ = q_.reshape(B, nq, cq, K, G, hd)
    k_ = k_.reshape(B, nkv, ckv, K, hd)
    v_ = v_.reshape(B, nkv, ckv, K, hd)
    qp = qp.reshape(B, nq, cq)
    kvp = kvp.reshape(B, nkv, ckv)

    def q_chunk(qi, q_blk, qp_blk):
        # online softmax over kv chunks
        def kv_step(carry, inp):
            acc, m, l = carry
            k_blk, v_blk, kp_blk = inp
            s = _attn_scores(q_blk, k_blk, scale, softcap_val)  # (B,K,G,cq,ckv)
            mask = _attn_mask(qp_blk, kp_blk, causal, window)
            s = jnp.where(mask[:, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgst,btkd->bkgsd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=f32)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, K, G, cq, hd), f32)
        m0 = jnp.full((B, K, G, cq), -jnp.inf, f32)
        l0 = jnp.zeros((B, K, G, cq), f32)
        (acc, m, l), _ = lax.scan(
            kv_step, (acc0, m0, l0),
            (k_.swapaxes(0, 1), v_.swapaxes(0, 1), kvp.swapaxes(0, 1)))
        o = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.einsum("bkgsd->bskgd", o).astype(q.dtype)

    # remat each q-chunk: backward recomputes its kv scan instead of
    # stashing (bq x bkv) score tiles per kv step
    out = lax.map(lambda args: jax.checkpoint(q_chunk)(*args),
                  (jnp.arange(nq), q_.swapaxes(0, 1), qp.swapaxes(0, 1)))
    out = out.swapaxes(0, 1).reshape(B, nq * cq, K, G, hd)
    return out[:, :S]


def cache_write(cache, new, pos):
    """Write per-sequence entries into a cache at per-sequence positions.

    cache: (B, S, ...); new: (B, ...); pos: (B,) int32. Returns updated cache.
    """
    def upd(c, n, p):
        return lax.dynamic_update_slice(c, n[None].astype(c.dtype),
                                        (p,) + (0,) * (c.ndim - 1))
    return jax.vmap(upd)(cache, new, pos)


def causal_conv1d(x, w, b, state=None):
    """Depthwise causal temporal conv.

    x: (B, S, D); w: (W, D); b: (D,).  state: (B, W-1, D) history or None.
    Returns (y, new_state) where new_state holds the trailing W-1 inputs.
    """
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], W - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # (B, S+W-1, D)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(W))
    new_state = xp[:, -(W - 1):] if W > 1 else state
    return (y + b).astype(x.dtype), new_state


# --------------------------------------------------------------------------
# attention block (dense / local / cross) — shared by most families
# --------------------------------------------------------------------------

def attn_template(cfg: ArchConfig, *, cross=False, heads=None, kv_heads=None):
    D, hd = cfg.d_model, cfg.head_dim
    H = heads or cfg.n_heads
    K = kv_heads or cfg.n_kv_heads
    t = {
        "wq": ParamSpec((D, H * hd), ("embed", "heads")),
        "wk": ParamSpec((D, K * hd), ("embed", "kv_heads")),
        "wv": ParamSpec((D, K * hd), ("embed", "kv_heads")),
        "wo": ParamSpec((H * hd, D), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        t["bq"] = ParamSpec((H * hd,), ("heads",), init="zeros")
        t["bk"] = ParamSpec((K * hd,), ("kv_heads",), init="zeros")
        t["bv"] = ParamSpec((K * hd,), ("kv_heads",), init="zeros")
    if cfg.qk_norm:
        t["q_norm"] = ParamSpec((hd,), (None,), init="zeros")
        t["k_norm"] = ParamSpec((hd,), (None,), init="zeros")
    return t


def _project_qkv(p, cfg, x, *, heads=None, kv_heads=None):
    H = heads or cfg.n_heads
    K = kv_heads or cfg.n_kv_heads
    hd = cfg.head_dim
    B, S, _ = x.shape
    q, k, v = dot(x, p["wq"]), dot(x, p["wk"]), dot(x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if S > 1:
        # head-sharded / seq-gathered attention layout: one all-gather
        # per layer here instead of one per (q-chunk, kv-chunk) tile
        # inside the online-softmax loops (fused dims always divide)
        q = constrain(q, "batch", None, "heads")
        k = constrain(k, "batch", None, "kv_heads")
        v = constrain(v, "batch", None, "kv_heads")
    q = q.reshape(B, S, K, H // K, hd)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def attn_apply(p, cfg, x, positions, *, kind="attn_global", heads=None,
               kv_heads=None, encoder_kv=None, make_cache=0):
    """Full-sequence attention.  Returns (y, cache|None).

    kind: attn_global | attn_local | attn_bidir | attn_cross.
    make_cache: if >0, emit a decode cache of that many slots.
    """
    B, S, _ = x.shape
    H = heads or cfg.n_heads
    K = kv_heads or cfg.n_kv_heads
    hd = cfg.head_dim
    q, k, v = _project_qkv(p, cfg, x, heads=heads, kv_heads=kv_heads)

    use_chunks = S > cfg.attn_chunk_threshold
    cq = cfg.attn_chunk_q if use_chunks else 0
    ckv = cfg.attn_chunk_kv if use_chunks else 0
    if kind == "attn_cross":
        ek, ev = encoder_kv
        kv_pos = jnp.broadcast_to(jnp.arange(ek.shape[1]), (B, ek.shape[1]))
        if cfg.use_rope:
            q = rope(q, positions, cfg.rope_theta)
        o = attention(q, ek, ev, q_positions=positions, kv_positions=kv_pos,
                      causal=False, chunk_q=cq, chunk_kv=ckv)
    else:
        causal = kind != "attn_bidir"
        window = cfg.window_size if kind == "attn_local" else None
        if causal and cfg.use_rope:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        o = attention(
            q, k, v, q_positions=positions, kv_positions=positions,
            causal=causal, window=window, softcap_val=cfg.attn_softcap,
            chunk_q=cq, chunk_kv=ckv)

    y = dot(o.reshape(B, S, H * hd), p["wo"])

    cache = None
    if make_cache and kind in ("attn_global", "attn_local"):
        slots = make_cache if kind == "attn_global" else min(
            make_cache, cfg.window_size)
        n = min(S, slots)
        tail_pos = positions[:, S - n:]
        kt, vt = k[:, S - n:], v[:, S - n:]
        quant = cfg.kv_quant == "int8" and kind == "attn_global"
        if quant:
            kt, ks = kv_quantize(kt)
            vt, vs = kv_quantize(vt)
        if kind == "attn_global" or n < slots:
            # global caches are position-indexed and prefill starts at
            # position 0, so the tail maps to slots [0, n) — a plain pad,
            # no scatter (scatters shard poorly and copy the cache)
            pad = ((0, 0), (0, slots - n), (0, 0), (0, 0))
            ck, cv = jnp.pad(kt, pad), jnp.pad(vt, pad)
            cp = jnp.pad(tail_pos, ((0, 0), (0, slots - n)),
                         constant_values=-1)
            if quant:
                ks = jnp.pad(ks, ((0, 0), (0, slots - n), (0, 0)))
                vs = jnp.pad(vs, ((0, 0), (0, slots - n), (0, 0)))
        else:
            # full local ring buffer: slot = position % window, which for
            # the last `slots` positions is a cyclic roll of the tail
            shift = tail_pos[0, 0] % slots  # uniform prefill positions
            ck = jnp.roll(kt, shift, axis=1)
            cv = jnp.roll(vt, shift, axis=1)
            cp = jnp.roll(tail_pos, shift, axis=1)
        cache = {"k": ck, "v": cv, "pos": cp}
        if quant:
            cache["k_scale"] = ks
            cache["v_scale"] = vs
    return y, cache


def kv_quantize(t):
    """Per (token, kv-head) symmetric int8: t (B, S, K, hd) ->
    (int8 codes, f32 scales (B, S, K))."""
    amax = jnp.max(jnp.abs(t.astype(f32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(t.astype(f32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _attn_decode_quant(q, cache, *, window, softcap_val, q_positions):
    """Decode attention over an int8 KV cache.

    The dequantization scale is folded *around* the integer dots —
    k's scale rescales the score column, v's scale rescales p before the
    PV dot — so no bf16 copy of the cache ever materializes.
    """
    scale = q.shape[-1] ** -0.5
    kq, ks = cache["k"], cache["k_scale"]  # (B,T,K,hd) i8, (B,T,K) f32
    vq, vs = cache["v"], cache["v_scale"]
    s = jnp.einsum("bskgd,btkd->bkgst", q.astype(f32), kq.astype(f32))
    s = s * ks.transpose(0, 2, 1)[:, :, None, None, :] * scale
    s = softcap(s, softcap_val)
    mask = _attn_mask(q_positions, cache["pos"], True, window)
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = p * vs.transpose(0, 2, 1)[:, :, None, None, :]
    o = jnp.einsum("bkgst,btkd->bskgd", p, vq.astype(f32))
    return o.astype(q.dtype)


def attn_decode(p, cfg, x, positions, cache, *, kind="attn_global",
                heads=None, kv_heads=None, encoder_kv=None):
    """Single-token attention with KV cache.  x: (B, 1, D); positions: (B,).

    Global caches are position-indexed (slot = position); local caches are
    ring buffers (slot = position % window) with explicit slot positions.
    """
    B = x.shape[0]
    H = heads or cfg.n_heads
    K = kv_heads or cfg.n_kv_heads
    hd = cfg.head_dim
    q, k, v = _project_qkv(p, cfg, x, heads=heads, kv_heads=kv_heads)

    if kind == "attn_cross":
        ek, ev = encoder_kv
        kv_pos = jnp.broadcast_to(jnp.arange(ek.shape[1]), (B, ek.shape[1]))
        if cfg.use_rope:
            q = rope(q, positions[:, None], cfg.rope_theta)
        o = attention(q, ek, ev, q_positions=positions[:, None],
                      kv_positions=kv_pos, causal=False)
        return dot(o.reshape(B, 1, H * hd), p["wo"]), cache

    if cfg.use_rope:
        q = rope(q, positions[:, None], cfg.rope_theta)
        k = rope(k, positions[:, None], cfg.rope_theta)
    slots = cache["k"].shape[1]
    slot = positions % slots if kind == "attn_local" else positions
    window = cfg.window_size if kind == "attn_local" else None
    if "k_scale" in cache:  # int8 KV cache
        kq, ks = kv_quantize(k)
        vq, vs = kv_quantize(v)
        new_cache = {
            "k": cache_write(cache["k"], kq[:, 0], slot),
            "v": cache_write(cache["v"], vq[:, 0], slot),
            "k_scale": cache_write(cache["k_scale"], ks[:, 0], slot),
            "v_scale": cache_write(cache["v_scale"], vs[:, 0], slot),
            "pos": cache_write(cache["pos"], positions, slot),
        }
        o = _attn_decode_quant(q, new_cache, window=window,
                               softcap_val=cfg.attn_softcap,
                               q_positions=positions[:, None])
        return dot(o.reshape(B, 1, H * hd), p["wo"]), new_cache
    new_cache = {
        "k": cache_write(cache["k"], k[:, 0], slot),
        "v": cache_write(cache["v"], v[:, 0], slot),
        "pos": cache_write(cache["pos"], positions, slot),
    }
    o = attention(q, new_cache["k"], new_cache["v"],
                  q_positions=positions[:, None], kv_positions=new_cache["pos"],
                  causal=True, window=window, softcap_val=cfg.attn_softcap)
    return dot(o.reshape(B, 1, H * hd), p["wo"]), new_cache


# --------------------------------------------------------------------------
# gated MLP (dense) and MoE
# --------------------------------------------------------------------------

def mlp_template(cfg: ArchConfig, d_ff=None):
    D, F = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wi": ParamSpec((D, 2 * F), ("embed", "ff")),  # fused gate+up
        "wo": ParamSpec((F, D), ("ff", "embed")),
    }


def mlp_apply(p, x):
    # pin the hidden to ff(model)-sharding: its cotangent then shards the
    # same way, which keeps dW_i = x^T @ d(hidden) ff-sharded instead of
    # replicated (a multi-GB fp32 buffer per period position otherwise)
    gu = constrain(dot(x, p["wi"]), "batch", None, "ff")
    g, u = jnp.split(gu, 2, axis=-1)
    h = constrain(jax.nn.gelu(g.astype(f32)).astype(x.dtype) * u,
                  "batch", None, "ff")
    return dot(h, p["wo"])


def moe_template(cfg: ArchConfig):
    D = cfg.d_model
    e = cfg.moe
    return {
        "router": ParamSpec((D, e.n_experts), ("embed", None)),
        # the leading expert axis is not the fan-in
        "wi": ParamSpec((e.n_experts, D, 2 * e.d_expert_ff),
                        ("experts", "embed", "ff"), scale=D ** -0.5),
        "wo": ParamSpec((e.n_experts, e.d_expert_ff, D),
                        ("experts", "ff", "embed"),
                        scale=e.d_expert_ff ** -0.5),
    }


def moe_takes_routed_path(n_tokens: int, group: int, cap: int,
                          n_experts: int, top_k: int) -> bool:
    """Whether ``moe_apply`` reads only the routed experts' weights.

    Taken when it reads fewer expert blocks than the capacity dispatch
    (``n_tokens * top_k < n_experts``), when no (token, slot) pair can
    be dropped (``cap >= group``), so the result is the capacity
    path's, and when no mesh is active: the kernel reads whole expert
    blocks of an unsharded stack.
    """
    return (n_tokens * top_k < n_experts and cap >= group
            and _current_mesh() is None)


_paths = threading.local()


@contextlib.contextmanager
def moe_paths():
    """Collect the path ("routed" / "capacity") each ``moe_apply``
    traced inside the block takes, into the set it yields."""
    prev = getattr(_paths, "seen", None)
    _paths.seen = seen = set()
    try:
        yield seen
    finally:
        _paths.seen = prev


def moe_apply(p, cfg, x, group_size=None, layer=None):
    """Switch-style capacity-based MoE with grouped one-hot dispatch, or
    (where ``moe_takes_routed_path``) the routed-expert kernel.

    x: (B, S, D).  ``layer``: when given, ``p["wi"]``/``p["wo"]`` are the
    whole (n_stack, E, ...) expert stacks and ``layer`` indexes them (the
    decode loop passes them so: a slice cannot fuse into the kernel).
    Returns (y, aux) where aux carries the router load (per-expert
    probability mass — the Level-B utilization signal) and the
    load-balancing loss term.
    """
    e = cfg.moe
    B, S, D = x.shape
    N = B * S
    gs = min(group_size or cfg.moe_group, N)
    G = N // gs
    xg = x.reshape(G, gs, D)

    logits = jnp.einsum("gsd,de->gse", xg, p["router"],
                        preferred_element_type=f32)
    probs = jax.nn.softmax(logits, axis=-1)  # (G, S, E) fp32
    top_p, top_e = lax.top_k(probs, e.top_k)  # (G, S, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    cap = max(int(e.capacity_factor * gs * e.top_k / e.n_experts), 1)
    onehot = jax.nn.one_hot(top_e, e.n_experts, dtype=f32)  # (G,S,k,E)
    routed = moe_takes_routed_path(N, gs, cap, e.n_experts, e.top_k)
    if getattr(_paths, "seen", None) is not None:
        _paths.seen.add("routed" if routed else "capacity")
    wi, wo = p["wi"], p["wo"]
    if routed:
        # imported on use: Pallas adds seconds to a process's import time
        from repro.kernels.ops import moe_routed_op
        if layer is None:
            wi, wo, layer = wi[None], wo[None], 0
        y = moe_routed_op(x.reshape(N, D), wi, wo, layer,
                          top_e.reshape(N, e.top_k),
                          top_p.reshape(N, e.top_k))
    else:
        if layer is not None:
            wi = lax.dynamic_index_in_dim(wi, layer, 0, keepdims=False)
            wo = lax.dynamic_index_in_dim(wo, layer, 0, keepdims=False)
        y = _moe_capacity(xg, wi, wo, top_p, onehot, cap)

    # aux: per-expert routed mass and Switch load-balancing loss
    load = onehot.sum((0, 1, 2)) / (N * e.top_k)  # fraction dispatched
    importance = probs.mean((0, 1))
    aux_loss = e.n_experts * jnp.sum(load * importance)
    aux = {"expert_load": load, "moe_aux_loss": aux_loss}
    return y.reshape(B, S, D), aux


def _moe_capacity(xg, wi, wo, top_p, onehot, cap):
    """One-hot capacity dispatch over every expert.  xg: (G, gs, D)."""
    G, gs, k, E = onehot.shape
    # position of each (token, slot) within its expert queue
    flat = onehot.reshape(G, gs * k, E)
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(G, gs, k, E)
    keep = (pos < cap) * onehot
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=f32)
    disp = jnp.einsum("gske,gskec->gsec", keep, pos_oh)  # (G,S,E,C)
    comb = jnp.einsum("gsk,gske,gskec->gsec", top_p, keep, pos_oh)
    # dispatch tensors: token groups over DP, experts over the EP axis;
    # bf16 is plenty for one-hot routing masks and halves their footprint
    disp = constrain(disp.astype(xg.dtype), "batch", None, "experts", None)
    comb = constrain(comb.astype(f32), "batch", None, "experts", None)

    xin = jnp.einsum("gsec,gsd->egcd", disp.astype(f32), xg.astype(f32),
                     preferred_element_type=f32).astype(xg.dtype)
    xin = constrain(xin, "experts", "batch", None, "embed")
    gu = jnp.einsum("egcd,edf->egcf", xin, wi,
                    preferred_element_type=f32).astype(xg.dtype)
    g, u = jnp.split(gu, 2, axis=-1)
    h = jax.nn.gelu(g.astype(f32)).astype(xg.dtype) * u
    hout = jnp.einsum("egcf,efd->egcd", h, wo,
                      preferred_element_type=f32)
    return jnp.einsum("gsec,egcd->gsd", comb, hout).astype(xg.dtype)


# --------------------------------------------------------------------------
# RG-LRU recurrent block (recurrentgemma / Griffin)
# --------------------------------------------------------------------------

def rglru_template(cfg: ArchConfig):
    D = cfg.d_model
    R = cfg.rglru_dim or D
    W = cfg.conv_width
    return {
        "wx": ParamSpec((D, R), ("embed", "ff")),  # recurrence branch in
        "wg": ParamSpec((D, R), ("embed", "ff")),  # gate branch in
        "wo": ParamSpec((R, D), ("ff", "embed")),
        "conv_w": ParamSpec((W, R), (None, "ff"), scale=1.0 / W),
        "conv_b": ParamSpec((R,), ("ff",), init="zeros"),
        "lam": ParamSpec((R,), ("ff",), init="ones"),  # Λ (decay logits)
        "w_a": ParamSpec((R, R), ("ff", None)),  # recurrence gate r_t
        "w_i": ParamSpec((R, R), ("ff", None)),  # input gate i_t
    }


_RGLRU_C = 8.0  # Griffin's fixed decay temperature


def _rglru_coeffs(p, u):
    """Gates and log-decay for RG-LRU.  u: (B, S, R) post-conv input."""
    u32 = u.astype(f32)
    r = jax.nn.sigmoid(jnp.einsum("bsr,rq->bsq", u32, p["w_a"].astype(f32)))
    i = jax.nn.sigmoid(jnp.einsum("bsr,rq->bsq", u32, p["w_i"].astype(f32)))
    log_a = -_RGLRU_C * r * jax.nn.softplus(p["lam"].astype(f32))  # (B,S,R)
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * i * u32
    return a, gated


def rglru_apply(p, cfg, x, *, make_cache=False):
    """Full-sequence RG-LRU block via associative scan."""
    B, S, D = x.shape
    u = dot(x, p["wx"])
    gate = jax.nn.gelu(dot(x, p["wg"]).astype(f32)).astype(x.dtype)
    u, conv_state = causal_conv1d(u, p["conv_w"], p["conv_b"])
    a, gated = _rglru_coeffs(p, u)

    def combine(c1, c2):
        a1, h1 = c1
        a2, h2 = c2
        return a1 * a2, h1 * a2 + h2

    _, h = lax.associative_scan(combine, (a, gated), axis=1)
    y = dot((h.astype(x.dtype) * gate), p["wo"])
    cache = None
    if make_cache:
        cache = {"h": h[:, -1].astype(f32), "conv": conv_state}
    return y, cache


def rglru_decode(p, cfg, x, cache):
    """One-step RG-LRU.  x: (B, 1, D); cache: {"h": (B,R) f32, "conv"}."""
    u = dot(x, p["wx"])
    gate = jax.nn.gelu(dot(x, p["wg"]).astype(f32)).astype(x.dtype)
    u, conv_state = causal_conv1d(u, p["conv_w"], p["conv_b"],
                                  state=cache["conv"])
    a, gated = _rglru_coeffs(p, u)
    h = cache["h"] * a[:, 0] + gated[:, 0]  # (B, R)
    y = dot((h[:, None].astype(x.dtype) * gate), p["wo"])
    return y, {"h": h, "conv": conv_state}


# --------------------------------------------------------------------------
# xLSTM blocks: mLSTM (matrix memory) and sLSTM (scalar memory)
# --------------------------------------------------------------------------

def mlstm_template(cfg: ArchConfig):
    D = cfg.d_model
    nh = cfg.lru_heads or cfg.n_heads
    return {
        "wq": ParamSpec((D, D), ("embed", "heads")),
        "wk": ParamSpec((D, D), ("embed", "heads")),
        "wv": ParamSpec((D, D), ("embed", "heads")),
        "wi": ParamSpec((D, nh), ("embed", None), scale=0.1),
        "wf": ParamSpec((D, nh), ("embed", None), scale=0.1),
        "bf": ParamSpec((nh,), (None,), init="ones"),
        "wg": ParamSpec((D, D), ("embed", "heads")),  # output gate branch
        "wo": ParamSpec((D, D), ("heads", "embed")),
    }


def _mlstm_gates(p, x):
    x32 = x.astype(f32)
    i_log = jnp.einsum("bsd,dh->bsh", x32, p["wi"].astype(f32))
    f_log = jax.nn.log_sigmoid(
        jnp.einsum("bsd,dh->bsh", x32, p["wf"].astype(f32))
        + p["bf"].astype(f32))
    return i_log, f_log


def mlstm_apply(p, cfg, x, *, make_cache=False):
    """Stabilized mLSTM, sequential scan over time (fp32 state).

    State per head: C (dh, dh) matrix memory, n (dh,) normalizer, m scalar
    stabilizer.  h_t = o_t * (C_t q_t / max(|n_t.q_t|, 1)).
    """
    B, S, D = x.shape
    nh = cfg.lru_heads or cfg.n_heads
    dh = D // nh
    q = dot(x, p["wq"]).reshape(B, S, nh, dh).astype(f32) * dh ** -0.5
    k = dot(x, p["wk"]).reshape(B, S, nh, dh).astype(f32) * dh ** -0.5
    v = dot(x, p["wv"]).reshape(B, S, nh, dh).astype(f32)
    og = jax.nn.sigmoid(dot(x, p["wg"]).astype(f32)).reshape(B, S, nh, dh)
    i_log, f_log = _mlstm_gates(p, x)

    def step(carry, inp):
        C, n, m = carry
        qt, kt, vt, ot, il, fl = inp
        m_new = jnp.maximum(fl + m, il)
        i_ = jnp.exp(il - m_new)
        f_ = jnp.exp(fl + m - m_new)
        C = f_[..., None, None] * C + i_[..., None, None] * (
            vt[..., :, None] * kt[..., None, :])
        n = f_[..., None] * n + i_[..., None] * kt
        num = jnp.einsum("bhij,bhj->bhi", C, qt)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhj,bhj->bh", n, qt)),
                          jnp.exp(-m_new))[..., None]
        h = ot * (num / den)
        return (C, n, m_new), h

    C0 = jnp.zeros((B, nh, dh, dh), f32)
    n0 = jnp.zeros((B, nh, dh), f32)
    m0 = jnp.zeros((B, nh), f32)
    xs = tuple(a.swapaxes(0, 1) for a in (q, k, v, og, i_log, f_log))
    (C, n, m), hs = lax.scan(step, (C0, n0, m0), xs)
    h = hs.swapaxes(0, 1).reshape(B, S, D).astype(x.dtype)
    y = dot(h, p["wo"])
    cache = {"C": C, "n": n, "m": m} if make_cache else None
    return y, cache


def mlstm_decode(p, cfg, x, cache):
    B = x.shape[0]
    nh = cfg.lru_heads or cfg.n_heads
    dh = x.shape[-1] // nh
    q = dot(x, p["wq"]).reshape(B, nh, dh).astype(f32) * dh ** -0.5
    k = dot(x, p["wk"]).reshape(B, nh, dh).astype(f32) * dh ** -0.5
    v = dot(x, p["wv"]).reshape(B, nh, dh).astype(f32)
    og = jax.nn.sigmoid(dot(x, p["wg"]).astype(f32)).reshape(B, nh, dh)
    il, fl = _mlstm_gates(p, x)
    il, fl = il[:, 0], fl[:, 0]
    C, n, m = cache["C"], cache["n"], cache["m"]
    m_new = jnp.maximum(fl + m, il)
    i_ = jnp.exp(il - m_new)
    f_ = jnp.exp(fl + m - m_new)
    C = f_[..., None, None] * C + i_[..., None, None] * (
        v[..., :, None] * k[..., None, :])
    n = f_[..., None] * n + i_[..., None] * k
    num = jnp.einsum("bhij,bhj->bhi", C, q)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhj,bhj->bh", n, q)),
                      jnp.exp(-m_new))[..., None]
    h = (og * (num / den)).reshape(B, 1, -1).astype(x.dtype)
    return dot(h, p["wo"]), {"C": C, "n": n, "m": m_new}


def slstm_template(cfg: ArchConfig):
    D = cfg.d_model
    nh = cfg.lru_heads or cfg.n_heads
    dh = D // nh
    t = {}
    for g in ("i", "f", "z", "o"):
        t[f"w{g}"] = ParamSpec((D, D), ("embed", "heads"))
        t[f"r{g}"] = ParamSpec((nh, dh, dh), (None, None, None), scale=0.1)
        t[f"b{g}"] = ParamSpec((D,), ("heads",), init="zeros")
    t["wo_out"] = ParamSpec((D, D), ("heads", "embed"))
    return t


def slstm_apply(p, cfg, x, *, make_cache=False):
    """Stabilized sLSTM with block-diagonal recurrence (sequential scan)."""
    B, S, D = x.shape
    nh = cfg.lru_heads or cfg.n_heads
    dh = D // nh
    pre = {g: (dot(x, p[f"w{g}"]) + p[f"b{g}"]).astype(f32)
              .reshape(B, S, nh, dh) for g in ("i", "f", "z", "o")}
    R = {g: p[f"r{g}"].astype(f32) for g in ("i", "f", "z", "o")}

    def step(carry, inp):
        c, n, h, m = carry  # (B, nh, dh) each; m: (B, nh, dh)
        xi, xf, xz, xo = inp
        rec = {g: jnp.einsum("bhj,hij->bhi", h, R[g])
               for g in ("i", "f", "z", "o")}
        il = xi + rec["i"]
        fl = jax.nn.log_sigmoid(xf + rec["f"])
        m_new = jnp.maximum(fl + m, il)
        i_ = jnp.exp(il - m_new)
        f_ = jnp.exp(fl + m - m_new)
        z = jnp.tanh(xz + rec["z"])
        o = jax.nn.sigmoid(xo + rec["o"])
        c = f_ * c + i_ * z
        n = jnp.maximum(f_ * n + i_, jnp.exp(-m_new))
        h_new = o * c / n
        return (c, n, h_new, m_new), h_new

    zeros = jnp.zeros((B, nh, dh), f32)
    carry0 = (zeros, zeros + 1e-6, zeros, zeros)
    xs = tuple(pre[g].swapaxes(0, 1) for g in ("i", "f", "z", "o"))
    (c, n, h, m), hs = lax.scan(step, carry0, xs)
    y = dot(hs.swapaxes(0, 1).reshape(B, S, D).astype(x.dtype), p["wo_out"])
    cache = {"c": c, "n": n, "h": h, "m": m} if make_cache else None
    return y, cache


def slstm_decode(p, cfg, x, cache):
    B, _, D = x.shape
    nh = cfg.lru_heads or cfg.n_heads
    dh = D // nh
    pre = {g: (dot(x, p[f"w{g}"]) + p[f"b{g}"]).astype(f32)
              .reshape(B, nh, dh) for g in ("i", "f", "z", "o")}
    c, n, h, m = cache["c"], cache["n"], cache["h"], cache["m"]
    rec = {g: jnp.einsum("bhj,hij->bhi", h, p[f"r{g}"].astype(f32))
           for g in ("i", "f", "z", "o")}
    il = pre["i"] + rec["i"]
    fl = jax.nn.log_sigmoid(pre["f"] + rec["f"])
    m_new = jnp.maximum(fl + m, il)
    i_ = jnp.exp(il - m_new)
    f_ = jnp.exp(fl + m - m_new)
    z = jnp.tanh(pre["z"] + rec["z"])
    o = jax.nn.sigmoid(pre["o"] + rec["o"])
    c = f_ * c + i_ * z
    n = jnp.maximum(f_ * n + i_, jnp.exp(-m_new))
    h_new = o * c / n
    y = dot(h_new.reshape(B, 1, D).astype(x.dtype), p["wo_out"])
    return y, {"c": c, "n": n, "h": h_new, "m": m_new}
