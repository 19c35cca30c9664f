"""Plain reference of the dense GQA decoder family, in float32.

Written from the published architecture (Llama / Mistral: RMSNorm before
each block, rotary embeddings on the first and second half of each head,
grouped-query attention with an optional sliding window, a SiLU-gated
MLP, a tied or separate LM head).  It imports nothing of the program and
takes nothing the program made: the weights come from ``weights.make``
and the inputs from the traffic generators.  Every matrix product runs at
``highest`` precision.

``precision="fp8"`` is the control: the same model with every matrix
product's operands rounded to float8 (e4m3) with one scale per tensor,
the step below the bf16 that the configurations state.

Memory: the forward runs one sequence at a time and attention one block
of queries at a time, so a long sequence fits beside nothing else.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
Q_BLOCK = 512


def _q8(x):
    """``x`` rounded to float8 with one scale per tensor."""
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(spec: str, a, b, precision: str):
    if precision == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


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


def to_f32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


# -- serving: the gap of a served token below the reference's best ---------


def _hashable(hf: Dict) -> tuple:
    return tuple(sorted((k, v) for k, v in hf.items()
                        if isinstance(v, (int, float, bool, str))
                        or v is None))


@functools.lru_cache(maxsize=None)
def _gap_fn(hf_items: tuple, precision: str):
    hf = dict(hf_items)

    @jax.jit
    def fn(params, seq, query):
        """seq: (S,) tokens; query: (S,) the token chosen after each
        position.  Returns the reference's best logit minus the query's,
        and the argmax of ``precision``'s logits, at every position."""
        with jax.default_matmul_precision("highest"):
            lg = logits(hf, params, seq, precision)
        best = lg.max(-1)
        got = jnp.take_along_axis(lg, query[:, None], -1)[:, 0]
        return best - got, jnp.argmax(lg, -1).astype(jnp.int32)

    return fn


def served_gaps(hf: Dict, params_f32: Dict, prompt: np.ndarray,
                served: np.ndarray, pad_to: int,
                precision: str = "f32") -> np.ndarray:
    """Gap of each served token below the reference's best logit at its
    position (teacher-forced on the served tokens).  Sequences are padded
    at the end to ``pad_to`` so that one program serves every request;
    under causal attention the padding changes no earlier position."""
    seq = np.concatenate([prompt, served]).astype(np.int32)
    n = seq.size
    assert n <= pad_to, (n, pad_to)
    padded = np.zeros(pad_to, np.int32)
    padded[:n] = seq
    query = np.zeros(pad_to, np.int32)
    query[:n - 1] = seq[1:]
    gap, _ = _gap_fn(_hashable(hf), precision)(params_f32, padded, query)
    lo = prompt.size - 1
    return np.asarray(gap)[lo:n - 1]


def control_gaps(hf: Dict, params_f32: Dict, prompt: np.ndarray,
                 served: np.ndarray, pad_to: int,
                 precision: str = "fp8") -> np.ndarray:
    """At each position of the same prompt and served tokens, the gap (in
    the float32 reference) of the token that ``precision`` puts first."""
    seq = np.concatenate([prompt, served]).astype(np.int32)
    n = seq.size
    padded = np.zeros(pad_to, np.int32)
    padded[:n] = seq
    _, top = _gap_fn(_hashable(hf), precision)(params_f32, padded, padded)
    gap, _ = _gap_fn(_hashable(hf), "f32")(params_f32, padded, top)
    lo = prompt.size - 1
    return np.asarray(gap)[lo:n - 1]


__all__ = ["logits", "to_f32", "served_gaps", "control_gaps"]
