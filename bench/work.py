"""The chip's side of a roofline: bytes per element of each stored type
and the least time an amount of work needs at the published peaks.

What a step or a kernel of one model family needs, in operations and
bytes, is counted from its configuration's shapes by that family's
module (``reference/<family>.py``: ``decode_step``).
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """Seconds the chip needs at its published peaks: the larger of the
    compute and the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
