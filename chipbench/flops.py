"""Operations and bytes that a step needs, computed from shapes.

These are the work the algorithm requires, not what a program happens to
execute: attention is counted at its causal (or windowed) need, recomputed
layers are not counted, and bytes are the least that a step must move.
``hf`` is a configuration file's dict (``configs/<name>.json``).  The
peaks and the arithmetic every family shares are here; the counts are the
family's, dispatched by ``model_type``.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Optional

from chipbench import families

BF16 = 2


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks; a device that is not in the table is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def attn_pairs(s: int, window: Optional[int] = None) -> int:
    """(query, key) pairs causal attention over ``s`` positions needs."""
    if not window or window >= s:
        return s * (s + 1) // 2
    w = int(window)
    return w * (w + 1) // 2 + (s - w) * w


def least_time(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The larger of the compute bound and the memory bound, in seconds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


# -- counts of the configuration's family (chipbench/families/<model_type>.py)


def layer_matmul_params(hf: Dict) -> int:
    """Weights of one decoder layer that take part in a matrix product."""
    return families.load(hf).layer_matmul_params(hf)


def head_params(hf: Dict) -> int:
    return families.load(hf).head_params(hf)


def matmul_params(hf: Dict) -> int:
    """All weights in matrix products per token: layers plus the LM head."""
    return families.load(hf).matmul_params(hf)


def weight_bytes(hf: Dict, dtype_bytes: int = BF16) -> int:
    """Bytes of every weight a decode step reads."""
    return families.load(hf).weight_bytes(hf, dtype_bytes)


def attn_flops(hf: Dict, s: int) -> int:
    """Forward attention FLOPs of one layer over one sequence."""
    return families.load(hf).attn_flops(hf, s)


def prefill_flops(hf: Dict, s: int) -> int:
    """Model FLOPs to prefill one ``s``-token prompt."""
    return families.load(hf).prefill_flops(hf, s)


def flash_call(hf: Dict, batch: int, s: int) -> Dict[str, float]:
    """FLOPs and bytes of one forward flash-attention call over ``batch``
    sequences of ``s`` tokens."""
    return families.load(hf).flash_call(hf, batch, s)


def decode_bytes(hf: Dict, live_positions: Iterable[int]) -> int:
    """Least bytes of one decode step."""
    return families.load(hf).decode_bytes(hf, live_positions)
