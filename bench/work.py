"""Operations and bytes a step needs, counted from a configuration's
shapes (not from what the program happens to do).

A decode step of one token at batch 1 needs every weight it multiplies
once: attention projections, router, the ``num_experts_per_tok``
experts it is routed to (not all experts), norms, one embedding row
and the tied head over the whole vocabulary; and it reads the keys and
values of the ``context`` positions before it and writes its own.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def decode_step(m: dict, context: float) -> tuple[float, float]:
    """(FLOPs, bytes) of one decoded token with ``context`` cached
    positions before it."""
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


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """Seconds the chip needs at its published peaks: the larger of the
    compute and the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
