"""Operations and bytes that a step needs, computed from shapes.

These are the work the algorithm requires, not what a program happens to
execute: attention is counted at its causal (or windowed) need, recomputed
layers are not counted, and bytes are the least that a step must move.
``hf`` is a configuration file's dict (``configs/<name>.json``).
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Optional

BF16 = 2


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks; a device that is not in the table is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def layer_matmul_params(hf: Dict) -> int:
    """Weights of one decoder layer that take part in a matrix product."""
    d, f, hd = hf["hidden_size"], hf["intermediate_size"], hf["head_dim"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    return d * hq * hd * 2 + d * hkv * hd * 2 + 3 * d * f


def head_params(hf: Dict) -> int:
    return hf["hidden_size"] * hf["vocab_size"]


def matmul_params(hf: Dict) -> int:
    """All weights in matrix products per token: layers plus the LM head."""
    return hf["num_hidden_layers"] * layer_matmul_params(hf) + head_params(hf)


def weight_bytes(hf: Dict, dtype_bytes: int = BF16) -> int:
    """Bytes of every weight a decode step reads: the layers, the norms
    and the LM head (the embedding rows looked up are negligible)."""
    d, n = hf["hidden_size"], hf["num_hidden_layers"]
    norms = (2 * n + 1) * d
    return (n * layer_matmul_params(hf) + head_params(hf) + norms) \
        * dtype_bytes


def attn_pairs(s: int, window: Optional[int] = None) -> int:
    """(query, key) pairs causal attention over ``s`` positions needs."""
    if not window or window >= s:
        return s * (s + 1) // 2
    w = int(window)
    return w * (w + 1) // 2 + (s - w) * w


def attn_flops(hf: Dict, s: int) -> int:
    """Forward attention FLOPs of one layer over one sequence (QK^T and PV)."""
    return 4 * hf["num_attention_heads"] * hf["head_dim"] * attn_pairs(
        s, hf.get("sliding_window"))


def prefill_flops(hf: Dict, s: int) -> int:
    """Model FLOPs to prefill one ``s``-token prompt: every layer over
    every position, and the LM head for the last position only (the
    server samples the first token from it)."""
    n = hf["num_hidden_layers"]
    return (2 * n * layer_matmul_params(hf) * s + n * attn_flops(hf, s)
            + 2 * head_params(hf))


def flash_call(hf: Dict, batch: int, s: int) -> Dict[str, float]:
    """FLOPs and bytes of one forward flash-attention call over ``batch``
    sequences of ``s`` tokens: Q and O at the query heads, K and V at the
    key-value heads, bf16."""
    hq, hkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    return {"flops": batch * 4 * hq * hd * attn_pairs(
                s, hf.get("sliding_window")),
            "bytes": batch * (2 * hq + 2 * hkv) * s * hd * BF16}


def least_time(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The larger of the compute bound and the memory bound, in seconds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def decode_bytes(hf: Dict, live_positions: Iterable[int]) -> int:
    """Least bytes of one decode step: the weights once, the K/V of each
    active row's live positions (capped at the window), and one new K/V
    row per active row, bf16."""
    n, hkv, hd = (hf["num_hidden_layers"], hf["num_key_value_heads"],
                  hf["head_dim"])
    w = hf.get("sliding_window")
    kv_row = n * 2 * hkv * hd * BF16
    total = weight_bytes(hf)
    for p in live_positions:
        total += kv_row * ((min(p, w) if w else p) + 1)
    return total
