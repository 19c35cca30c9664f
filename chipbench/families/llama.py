"""The dense GQA decoder family (Llama / Mistral): the program's settings,
the parameter shapes, the plain reference and the operation counts.

Written from the published architecture: RMSNorm before each block,
rotary embeddings on the first and second half of each head, grouped-query
attention with an optional sliding window, a SiLU-gated MLP, a tied or
separate LM head.  The layers are stacked on a leading axis, as the
program's dense model takes them.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import BF16, attn_pairs
from chipbench.reference import Q_BLOCK, _mm, _rms

# -- the program --------------------------------------------------------------

#: configuration-file key -> ModelConfig field
FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "head_dim": "head_dim",
    "sliding_window": "window",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "hidden_act": "activation",
    "torch_dtype": "param_dtype",
}


def program_changes(hf: Dict) -> Dict:
    changes = {field: hf[key] for key, field in FIELDS.items()}
    changes["compute_dtype"] = hf["torch_dtype"]
    changes["gated_mlp"] = True
    return changes


# -- the weights --------------------------------------------------------------


def shapes(hf: Dict) -> Dict:
    d = hf["hidden_size"]
    f = hf["intermediate_size"]
    n = hf["num_hidden_layers"]
    hd = hf["head_dim"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    v = hf["vocab_size"]
    out = {
        "embed": (v, d),
        "final_norm/scale": (d,),
        "layers/ln1/scale": (n, d),
        "layers/ln2/scale": (n, d),
        "layers/attn/wq": (n, d, hq * hd),
        "layers/attn/wk": (n, d, hkv * hd),
        "layers/attn/wv": (n, d, hkv * hd),
        "layers/attn/wo": (n, hq * hd, d),
        "layers/mlp/w_up": (n, d, f),
        "layers/mlp/w_gate": (n, d, f),
        "layers/mlp/w_down": (n, f, d),
    }
    if not hf["tie_word_embeddings"]:
        out["lm_head"] = (d, v)
    return out


def std(hf: Dict, path: str, shape: Tuple[int, ...]) -> float:
    """Matrices N(0, 1/fan_in); the embedding and an untied LM head
    N(0, 4/hidden_size), which gives logits of about unit-2 spread."""
    if path in ("embed", "lm_head"):
        return 2.0 / np.sqrt(hf["hidden_size"])
    return 1.0 / np.sqrt(shape[-2])


# -- the plain reference ------------------------------------------------------


def _rope(x, pos, theta):
    """x: (H, S, D); rotate first half against second half."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(hf, q, k, v, precision):
    """q: (Hq, S, D), k/v: (Hkv, S, D) -> (Hq, S, D), causal, windowed."""
    hq, s, d = q.shape
    group = hq // k.shape[0]
    k = jnp.repeat(k, group, axis=0)
    v = jnp.repeat(v, group, axis=0)
    window = hf.get("sliding_window")
    cols = jnp.arange(s)[None, :]
    outs = []
    for lo in range(0, s, Q_BLOCK):
        hi = min(s, lo + Q_BLOCK)
        sc = _mm("hqd,hkd->hqk", q[:, lo:hi], k, precision) / np.sqrt(d)
        rows = jnp.arange(lo, hi)[:, None]
        mask = cols <= rows
        if window:
            mask &= cols > rows - window
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(_mm("hqk,hkd->hqd", p, v, precision))
    return jnp.concatenate(outs, axis=1)


def _layer(hf, precision, x, lp, pos):
    s = x.shape[0]
    hd = hf["head_dim"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    eps = hf["rms_norm_eps"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    a = lp["attn"]
    q = _mm("sd,dh->sh", h, a["wq"], precision).reshape(s, hq, hd)
    k = _mm("sd,dh->sh", h, a["wk"], precision).reshape(s, hkv, hd)
    v = _mm("sd,dh->sh", h, a["wv"], precision).reshape(s, hkv, hd)
    q = _rope(q.transpose(1, 0, 2), pos, hf["rope_theta"])
    k = _rope(k.transpose(1, 0, 2), pos, hf["rope_theta"])
    o = _attention(hf, q, k, v.transpose(1, 0, 2), precision)
    o = o.transpose(1, 0, 2).reshape(s, hq * hd)
    x = x + _mm("sh,hd->sd", o, a["wo"], precision)
    h = _rms(x, lp["ln2"]["scale"], eps)
    m = lp["mlp"]
    g = jax.nn.silu(_mm("sd,df->sf", h, m["w_gate"], precision))
    u = _mm("sd,df->sf", h, m["w_up"], precision)
    return x + _mm("sf,fd->sd", g * u, m["w_down"], precision)


def logits(hf: Dict, params: Dict, tokens, precision: str = "f32"):
    """(S,) int tokens -> (S, V) float32 logits."""
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[0])
    body = functools.partial(_layer, hf, precision)

    def step(h, lp):
        return body(h, lp, pos), None

    x, _ = jax.lax.scan(step, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], hf["rms_norm_eps"])
    head = (params["embed"].T if hf["tie_word_embeddings"]
            else params["lm_head"])
    return _mm("sd,dv->sv", x, head, precision)


# -- operations and bytes -----------------------------------------------------


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
