"""jit'd model-layout wrappers around the Pallas kernels.

The model keeps GQA activations as (B, S, K, G, hd); these wrappers
transpose into kernel layout, invoke the kernel (interpret=True on CPU
so the kernel body is executed for validation; compiled on real TPU),
and transpose back.  The attention ops are drop-in replacements for the
XLA-path attention in ``repro.models.layers`` when
``cfg.attn_impl == "pallas"``; ``moe_routed_op`` is what
``layers.moe_apply`` calls where it takes the routed path.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_decode import moe_routed_decode
from repro.kernels.rglru_scan import rglru_scan


def _on_cpu():
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("causal", "window", "softcap"))
def attention_op(q, k, v, *, causal=True, window=None, softcap=None):
    """q: (B, S, K, G, hd); k, v: (B, T, K, hd) -> (B, S, K, G, hd)."""
    B, S, K, G, hd = q.shape
    qh = q.transpose(0, 2, 3, 1, 4).reshape(B, K * G, S, hd)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    o = flash_attention(qh, kh, vh, causal=causal, window=window,
                        softcap=softcap, interpret=_on_cpu())
    return o.reshape(B, K, G, S, hd).transpose(0, 3, 1, 2, 4)


@partial(jax.jit, static_argnames=("window", "softcap"))
def decode_attention_op(q, k, v, q_pos, kv_pos, *, window=None,
                        softcap=None):
    """q: (B, 1, K, G, hd); k, v: (B, T, K, hd) cache -> (B, 1, K, G, hd)."""
    B, _, K, G, hd = q.shape
    o = decode_attention(q[:, 0], k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3), q_pos, kv_pos,
                         window=window, softcap=softcap,
                         interpret=_on_cpu())
    return o[:, None]


@jax.jit
def rglru_op(a, gated, h0=None):
    """Diagonal linear recurrence in model layout (B, S, R)."""
    return rglru_scan(a, gated, h0, interpret=_on_cpu())


@jax.jit
def moe_routed_op(x, wi, wo, layer, ids, weights):
    """x: (N, D) tokens; wi: (n_stack, E, D, 2F), wo: (n_stack, E, F, D)
    expert stacks, of which ``layer`` is used; ids, weights: (N, k)
    routed experts and their combine weights -> (N, D)."""
    return moe_routed_decode(x, wi, wo, layer, ids, weights,
                             interpret=_on_cpu())
