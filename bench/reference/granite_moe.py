"""Plain float32 forward of Granite-MoE (granite-3.0-1b-a400m family).

Each layer: pre-norm grouped-query attention with rotary positions,
then a top-k router over ``num_local_experts`` gated-MLP experts.  The
router's softmax runs over all experts, the k largest are kept and
renormalized to sum to 1.  Every expert is computed on every token and
weighted by its routing weight (zero where not chosen): plain, not
fast.

Departures from the published description, each one what the served
program computes:
- the expert and dense MLP gate is GELU (tanh form), not SiLU;
- no embedding, attention, residual or logits multipliers; attention
  scales scores by 1/sqrt(head_dim);
- norm scales are stored as offsets from 1;
- the program routes the prompt as one group with a per-expert
  capacity of floor(capacity_factor * tokens * k / experts): an
  expert's tokens past its capacity, in position order, get nothing
  from it.  Each decoded token is routed alone, which never drops.
  ``forward`` applies the same rule to the first ``prompt_len``
  positions and none to the rest; the published model drops nothing.

``program_sizes`` and ``decode_step`` read the same keys of the
configuration file for the harness's size check and for the decode
step's share of the chip's peak.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.common import (
    REPLACED, Quant, decoder, ein, gelu_tanh, normal_leaf, seeded_experts,
    seeded_weights,
)
from work import BYTES


def dims(m):
    D, H = m["hidden_size"], m["num_attention_heads"]
    hd = D // H
    return (D, H, m["num_key_value_heads"], hd, m["intermediate_size"],
            m["num_local_experts"], m["num_hidden_layers"],
            m["vocab_size"])


def template(m):
    """The served parameter tree: names, stacked shapes, fan-in std."""
    D, H, K, hd, F, E, L, V = dims(m)
    return {
        "embed": normal_leaf((V, D), 1.0),
        "final_norm": normal_leaf((D,)),
        "layers": {"scan": {"pos0": {
            "attn": {
                "wq": normal_leaf((L, D, H * hd), D ** -0.5),
                "wk": normal_leaf((L, D, K * hd), D ** -0.5),
                "wv": normal_leaf((L, D, K * hd), D ** -0.5),
                "wo": normal_leaf((L, H * hd, D), (H * hd) ** -0.5),
            },
            "ln1": normal_leaf((L, D)),
            "ln2": normal_leaf((L, D)),
            "moe": {
                "router": normal_leaf((L, D, E), D ** -0.5),
                # drawn, then replaced expert by expert (see weights)
                "wi": normal_leaf((L, E, D, 2 * F), REPLACED),
                "wo": normal_leaf((L, E, F, D), REPLACED),
            },
        }}},
    }


def weights(m, seed):
    """float32 copies of the served weights of seed ``seed``."""
    D, H, K, hd, F, E, L, V = dims(m)
    p = seeded_weights(template(m), seed, jnp.dtype(m["dtype"]))
    layers = p.pop("layers")["scan"]["pos0"]
    layers["moe"].update(seeded_experts(
        seed, E, [("wi", (L, D, 2 * F)), ("wo", (L, F, D))],
        jnp.dtype(m["dtype"])))
    p["layers"] = layers
    return p


def capacity(m, prompt_len):
    return max(int(m["capacity_factor"] * prompt_len
                   * m["num_experts_per_tok"]
                   / m["num_local_experts"]), 1)


def moe(p, x, m, prompt_len, quant: Quant = None):
    E, k = m["num_local_experts"], m["num_experts_per_tok"]
    S = x.shape[0]
    xr = x if quant is None else quant(x, -1)
    probs = jax.nn.softmax(ein("sd,de->se", x, p["router"]), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    chosen = jax.nn.one_hot(top_e, E, dtype=x.dtype)          # (S, k, E)
    assigned = chosen.sum(1)                                   # (S, E)
    weight = ein("sk,ske->se", top_p, chosen)
    in_prompt = (jnp.arange(S) < prompt_len)[:, None]
    before = jnp.cumsum(assigned * in_prompt, axis=0) - assigned
    kept = jnp.where(in_prompt, before < capacity(m, prompt_len), True)
    weight = weight * kept
    wi, wo = p["wi"], p["wo"]
    if quant is not None:
        wi, wo = quant(wi, 1), quant(wo, 1)
    g, u = jnp.split(ein("sd,edf->esf", xr, wi), 2, axis=-1)
    h = gelu_tanh(g) * u
    if quant is not None:
        h = quant(h, -1)
    return ein("se,esd->sd", weight, ein("esf,efd->esd", h, wo))


def forward(params, tokens, m, prompt_len, quant: Quant = None):
    """(S, vocab) float32 logits of ``tokens`` (S,), the first
    ``prompt_len`` of which were routed as one prefill group."""
    return decoder(params, tokens, m,
                   lambda p, h: moe(p["moe"], h, m, prompt_len, quant),
                   quant)


def program_sizes(m):
    """The program's ``ArchConfig`` attribute paths and the values the
    configuration file states for them."""
    return {
        "d_model": m["hidden_size"],
        "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"],
        "head_dim": m["hidden_size"] // m["num_attention_heads"],
        "n_layers": m["num_hidden_layers"],
        "vocab": m["vocab_size"],
        "rope_theta": m["rope_theta"],
        "tie_embeddings": m["tie_word_embeddings"],
        "dtype": m["dtype"],
        "norm_eps": m["rms_norm_eps"],
        "moe.n_experts": m["num_local_experts"],
        "moe.top_k": m["num_experts_per_tok"],
        "moe.d_expert_ff": m["intermediate_size"],
        "moe.capacity_factor": m["capacity_factor"],
        "moe_group": m["moe_group"],
    }


def decode_step(m: dict, context: float) -> tuple[float, float]:
    """(FLOPs, bytes) of one decoded token with ``context`` cached
    positions before it, counted from the configuration's shapes (not
    from what the program happens to do).

    A decode step of one token at batch 1 needs every weight it
    multiplies once: attention projections, router, the
    ``num_experts_per_tok`` experts it is routed to (not all experts),
    norms, one embedding row and the tied head over the whole
    vocabulary; and it reads the keys and values of the ``context``
    positions before it and writes its own."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    K, F = m["num_key_value_heads"], m["intermediate_size"]
    L, V = m["num_hidden_layers"], m["vocab_size"]
    hd = D // H
    attn = D * H * hd + 2 * D * K * hd + H * hd * D
    ff = D * m["num_local_experts"] + \
        m["num_experts_per_tok"] * (D * 2 * F + F * D)
    matmul_params = L * (attn + ff) + V * D
    params = matmul_params + L * 2 * D + D + D   # norms, embedding row
    width = BYTES[m["dtype"]]
    kv_read = L * 2 * context * K * hd * width
    kv_write = L * 2 * K * hd * width
    flops = 2 * matmul_params + L * 2 * 2 * (context + 1) * H * hd
    return float(flops), float(params * width + kv_read + kv_write)
