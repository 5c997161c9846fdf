"""Pallas TPU kernels for the serving hot spots.

  flash_attention    blocked causal/windowed prefill attention
  decode_attention   one-token GQA attention over a long KV cache
  rglru_scan         the RG-LRU linear recurrence
  moe_routed_decode  decode MoE over only the routed experts' blocks of
                     the stacked expert weights; on the model path
                     where ``layers.moe_apply`` routes few tokens
                     (decode at batch 1)

Each kernel ships as <name>.py (pl.pallas_call + BlockSpec), a jit'd
wrapper in ops.py, and a pure-jnp oracle in ref.py.  On CPU the kernels
run in interpret mode (the body executes in Python) — the TPU is the
compilation target, the oracle the correctness contract.
"""

from repro.kernels.flash_attention import flash_attention  # noqa: F401
from repro.kernels.decode_attention import decode_attention  # noqa: F401
from repro.kernels.rglru_scan import rglru_scan  # noqa: F401
from repro.kernels.moe_decode import moe_routed_decode  # noqa: F401
from repro.kernels import ops, ref  # noqa: F401
