"""Routed-expert MoE Pallas kernel for decode (TPU target).

At decode a batch of a few tokens is routed to ``top_k`` experts each,
so reading every expert's weights (the capacity dispatch does) moves
E / top_k times the bytes the token needs.  This kernel reads only the
routed ones: the expert ids, their combine weights and the layer index
are scalar-prefetched, and the BlockSpec index maps pick the block
``(layer, expert)`` of the whole stacked weight arrays, so only routed
blocks are DMA'd from HBM (and no per-layer slice of the stack is
materialized).

Grid: (tokens * top_k,) routed slots; slot s belongs to token
s // top_k.  Each step multiplies the (padded) tokens by one expert's
``wi`` (D, 2F) and ``wo`` (F, D) and adds the slot's token row, scaled
by its combine weight, into an f32 VMEM accumulator.  The casts follow
``models.layers.moe_apply``'s capacity path: gate/up accumulated in f32
and cast to the activation dtype, gelu in f32, the expert output in
f32, the combine in f32, one cast at the end.  Blocks at published
widths (granite-moe, bf16): ``wi`` 2 MiB, ``wo`` 1 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
SUBLANES = 8


def _kernel(layer_ref, ids_ref, w_ref, x_ref, wi_ref, wo_ref, o_ref,
            acc_ref, *, top_k, d_ff):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                        # (T, D)
    gu = jnp.dot(x, wi_ref[...],
                 preferred_element_type=f32).astype(x.dtype)  # (T, 2F)
    g, u = gu[:, :d_ff], gu[:, d_ff:]
    h = jax.nn.gelu(g.astype(f32)).astype(x.dtype) * u
    hout = jnp.dot(h, wo_ref[...], preferred_element_type=f32)  # (T, D)
    row = lax.broadcasted_iota(jnp.int32, hout.shape, 0)
    coef = jnp.where(row == s // top_k, w_ref[s], 0.0)
    acc_ref[...] += coef * hout

    @pl.when(s == pl.num_programs(0) - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_routed_decode(x, wi, wo, layer, ids, weights, *, interpret=False):
    """Sum over each token's routed experts of weight · expert(token).

    x: (N, D) tokens; wi: (n_stack, E, D, 2F) and wo: (n_stack, E, F, D)
    stacked expert weights, of which layer ``layer`` (int32 scalar) is
    used; ids: (N, k) int32 routed expert ids; weights: (N, k) their
    combine weights.  Returns (N, D) in x's dtype.
    """
    N, D = x.shape
    k = ids.shape[1]
    F = wo.shape[2]
    T = -(-N // SUBLANES) * SUBLANES
    xp = jnp.pad(x, ((0, T - N), (0, 0)))
    blocks = (D * 2 * F + F * D) * wi.dtype.itemsize
    kernel = functools.partial(_kernel, top_k=k, d_ff=F)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N * k,),
            in_specs=[
                pl.BlockSpec((T, D), lambda s, t, e, w: (0, 0)),
                pl.BlockSpec((None, None, D, 2 * F),
                             lambda s, t, e, w: (t[0], e[s], 0, 0)),
                pl.BlockSpec((None, None, F, D),
                             lambda s, t, e, w: (t[0], e[s], 0, 0)),
            ],
            out_specs=pl.BlockSpec((T, D), lambda s, t, e, w: (0, 0)),
            scratch_shapes=[pltpu.VMEM((T, D), f32)],
        ),
        out_shape=jax.ShapeDtypeStruct((T, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the routed blocks, double-buffered, with room for the rest
            vmem_limit_bytes=max(32 * 2**20, 2 * blocks + 8 * 2**20)),
        name="moe_routed_decode",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      ids.reshape(N * k).astype(jnp.int32),
      weights.reshape(N * k).astype(f32), xp, wi, wo)[:N]
