"""The DeepSeek-V2 family (``model_type`` ``deepseek_v2``): the program's
settings, the parameter shapes, the plain reference and the operation
counts.

Written from the DeepSeek-V2 report (arXiv:2405.04434, §2.1 multi-head
latent attention, §2.2 DeepSeekMoE) and the published configuration:
RMSNorm before each block; attention whose keys and values come from a
normed ``kv_lora_rank`` latent and one rope key shared by every head,
with the query taken straight from the input when ``q_lora_rank`` is
null; YaRN rope on (2i, 2i+1) pairs; ``first_k_dense_replace`` leading
layers with a SiLU-gated MLP, then MoE layers: a softmax over all routed
experts, the greedy top-k, the k weights renormalised only under
``norm_topk_prob`` and scaled by ``routed_scaling_factor`` (the program
takes only 1), plus the shared experts as one MLP.

The chip's share of an expert-parallel deployment: ``n_routed_experts``
in the file counts the experts held here, ``published`` the router's
outputs, and ``expert_parallel.first_expert`` where the held ones start.
The router keeps its published width; the layer adds the held experts'
part only, in the program and in the reference alike.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import BF16, attn_pairs
from chipbench.reference import Q_BLOCK, _mm, _rms

# -- the program --------------------------------------------------------------


def _check(hf: Dict) -> None:
    """Refuse settings the program does not implement."""
    fixed = {"moe_layer_freq": 1, "topk_method": "greedy",
             "scoring_func": "softmax", "n_group": 1, "topk_group": 1,
             "attention_bias": False, "hidden_act": "silu",
             "routed_scaling_factor": 1}
    bad = {k: hf[k] for k, v in fixed.items() if hf[k] != v}
    if hf["rope_scaling"]["type"] != "yarn":
        bad["rope_scaling.type"] = hf["rope_scaling"]["type"]
    if bad:
        raise ValueError(f"deepseek_v2: unsupported settings {bad}")


def router_width(hf: Dict) -> int:
    """The router's outputs: the published count of routed experts."""
    return hf["published"]["n_routed_experts"]


def first_expert(hf: Dict) -> int:
    return hf["expert_parallel"]["first_expert"]


def program_changes(hf: Dict) -> Dict:
    _check(hf)
    rs = hf["rope_scaling"]
    return {
        "family": "moe",
        "attn_type": "mla",
        "n_layers": hf["num_hidden_layers"],
        "first_dense_layers": hf["first_k_dense_replace"],
        "d_model": hf["hidden_size"],
        "n_heads": hf["num_attention_heads"],
        "n_kv_heads": hf["num_key_value_heads"],
        "d_ff": hf["moe_intermediate_size"],
        "dense_d_ff": hf["intermediate_size"],
        "vocab_size": hf["vocab_size"],
        "q_lora_rank": hf["q_lora_rank"] or 0,
        "kv_lora_rank": hf["kv_lora_rank"],
        "qk_rope_dim": hf["qk_rope_head_dim"],
        "qk_nope_dim": hf["qk_nope_head_dim"],
        "v_head_dim": hf["v_head_dim"],
        "head_dim": hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
        "n_experts": router_width(hf),
        "experts_held": hf["n_routed_experts"],
        "expert_offset": first_expert(hf),
        "experts_per_token": hf["num_experts_per_tok"],
        "n_shared_experts": hf["n_shared_experts"],
        "norm_topk_prob": hf["norm_topk_prob"],
        "capacity_factor": None,
        "rope_theta": float(hf["rope_theta"]),
        "rope_scaling": tuple(sorted((k, float(v)) for k, v in rs.items()
                                     if k != "type")),
        "rope_interleave": True,
        "norm_eps": hf["rms_norm_eps"],
        "tie_embeddings": hf["tie_word_embeddings"],
        "activation": hf["hidden_act"],
        "gated_mlp": True,
        "param_dtype": hf["torch_dtype"],
        "compute_dtype": hf["torch_dtype"],
    }


# -- the weights --------------------------------------------------------------


def _attn_shapes(hf: Dict) -> Dict:
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    dn, dr, dv = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                  hf["v_head_dim"])
    r = hf["kv_lora_rank"]
    assert not hf["q_lora_rank"], "a q-LoRA is not in this family file"
    return {"attn/w_q": (d, h * (dn + dr)), "attn/w_dkv": (d, r + dr),
            "attn/kv_norm/scale": (r,), "attn/w_uk": (r, h * dn),
            "attn/w_uv": (r, h * dv), "attn/wo": (h * dv, d)}


def shapes(hf: Dict) -> Dict:
    d, v = hf["hidden_size"], hf["vocab_size"]
    nd = hf["first_k_dense_replace"]
    nm = hf["num_hidden_layers"] - nd
    big_f, f = hf["intermediate_size"], hf["moe_intermediate_size"]
    fs = f * hf["n_shared_experts"]
    held = hf["n_routed_experts"]
    layer = {**_attn_shapes(hf), "ln1/scale": (d,), "ln2/scale": (d,)}
    dense = {**layer, "mlp/w_up": (d, big_f), "mlp/w_gate": (d, big_f),
             "mlp/w_down": (big_f, d)}
    moe = {**layer, "moe/router": (d, router_width(hf)),
           "moe/w_up": (held, d, f), "moe/w_gate": (held, d, f),
           "moe/w_down": (held, f, d),
           "moe/shared/w_up": (d, fs), "moe/shared/w_gate": (d, fs),
           "moe/shared/w_down": (fs, d)}
    out = {"embed": (v, d), "final_norm/scale": (d,), "lm_head": (d, v)}
    if nd:
        out.update({f"dense_layers/{k}": (nd,) + s for k, s in dense.items()})
    out.update({f"layers/{k}": (nm,) + s for k, s in moe.items()})
    return out


def std(hf: Dict, path: str, shape: Tuple[int, ...]) -> float:
    """Matrices N(0, 1/fan_in); the embedding, the LM head and the router
    N(0, 4/hidden_size): logits of about unit-2 spread, and router logits
    whose top 6 of 64 softmax outputs hold about half the mass, as a
    trained router's do (assumed)."""
    if path in ("embed", "lm_head") or path.endswith("/router"):
        return 2.0 / np.sqrt(hf["hidden_size"])
    return 1.0 / np.sqrt(shape[-2])


# -- the plain reference ------------------------------------------------------


def _yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_range(hf: Dict) -> Tuple[int, int]:
    """YaRN's (low, high): the rotary dims that turn ``beta_fast`` and
    ``beta_slow`` times over the original context, floored and ceiled."""
    rs = hf["rope_scaling"]
    dim, base = hf["qk_rope_head_dim"], hf["rope_theta"]

    def dim_at(rot):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    return (max(math.floor(dim_at(rs["beta_fast"])), 0),
            min(math.ceil(dim_at(rs["beta_slow"])), dim - 1))


def inv_freq(hf: Dict) -> np.ndarray:
    """YaRN inverse frequencies of the rope dims (float64): the plain
    ``base^(-2i/dim)`` below ``low``, divided by ``factor`` above
    ``high``, a linear ramp between."""
    rs = hf["rope_scaling"]
    dim, base = hf["qk_rope_head_dim"], hf["rope_theta"]
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / rs["factor"]
    low, high = yarn_range(hf)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def softmax_scale(hf: Dict) -> float:
    """``(nope + rope)^-1/2 · mscale(factor, mscale_all_dim)^2``."""
    rs = hf["rope_scaling"]
    m = _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(hf, x, pos):
    """x: (..., S, 64); the published code de-interleaves each vector into
    its even then odd dims and rotates the halves, which rotates the
    pairs (2i, 2i+1); the result keeps the de-interleaved layout, in the
    query and the key alike."""
    rs = hf["rope_scaling"]
    d = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv_freq(hf),
                                                         jnp.float32)
    m = (_yarn_mscale(rs["factor"], rs["mscale"])
         / _yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(hf, precision, x, a, pos):
    """Latent attention, expanded: per-head keys and values from the
    normed latent, the shared rope key broadcast to every head."""
    s = x.shape[0]
    h = hf["num_attention_heads"]
    dn, dr, dv = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                  hf["v_head_dim"])
    r = hf["kv_lora_rank"]
    q = _mm("sd,dh->sh", x, a["w_q"], precision).reshape(s, h, dn + dr)
    q = q.transpose(1, 0, 2)                                  # (H, S, 192)
    q = jnp.concatenate([q[..., :dn], _rope(hf, q[..., dn:], pos)], -1)
    ckv = _mm("sd,dc->sc", x, a["w_dkv"], precision)
    c = _rms(ckv[:, :r], a["kv_norm"]["scale"], hf["rms_norm_eps"])
    k_pe = _rope(hf, ckv[:, r:], pos)                         # (S, 64)
    k_nope = _mm("sc,ch->sh", c, a["w_uk"], precision).reshape(s, h, dn)
    v = _mm("sc,ch->sh", c, a["w_uv"], precision).reshape(s, h, dv)
    k = jnp.concatenate([k_nope.transpose(1, 0, 2),
                         jnp.broadcast_to(k_pe, (h, s, dr))], -1)
    v = v.transpose(1, 0, 2)
    scale = softmax_scale(hf)
    cols = jnp.arange(s)[None, :]
    outs = []
    for lo in range(0, s, Q_BLOCK):
        hi = min(s, lo + Q_BLOCK)
        sc = _mm("hqd,hkd->hqk", q[:, lo:hi], k, precision) * scale
        mask = cols <= jnp.arange(lo, hi)[:, None]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        outs.append(_mm("hqk,hkd->hqd", jax.nn.softmax(sc, axis=-1), v,
                        precision))
    o = jnp.concatenate(outs, axis=1).transpose(1, 0, 2).reshape(s, h * dv)
    return _mm("sh,hd->sd", o, a["wo"], precision)


def _mlp(precision, x, w_gate, w_up, w_down):
    g = jax.nn.silu(_mm("sd,df->sf", x, w_gate, precision))
    u = _mm("sd,df->sf", x, w_up, precision)
    return _mm("sf,fd->sd", g * u, w_down, precision)


def _moe(hf, precision, x, m):
    """Softmax over every router output, the greedy top-k, then each held
    expert in turn on the tokens that chose it, and the shared experts."""
    probs = jax.nn.softmax(_mm("sd,de->se", x, m["router"], precision), -1)
    w, idx = jax.lax.top_k(probs, hf["num_experts_per_tok"])
    if hf["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    w = w * hf["routed_scaling_factor"]
    y = _mlp(precision, x, m["shared"]["w_gate"], m["shared"]["w_up"],
             m["shared"]["w_down"])
    first = first_expert(hf)
    for e in range(hf["n_routed_experts"]):
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)  # (S,)
        y = y + gate[:, None] * _mlp(precision, x, m["w_gate"][e],
                                     m["w_up"][e], m["w_down"][e])
    return y


def _layer(hf, precision, pos, x, lp):
    eps = hf["rms_norm_eps"]
    x = x + _attention(hf, precision, _rms(x, lp["ln1"]["scale"], eps),
                       lp["attn"], pos)
    h = _rms(x, lp["ln2"]["scale"], eps)
    if "moe" in lp:
        return x + _moe(hf, precision, h, lp["moe"])
    return x + _mlp(precision, h, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                    lp["mlp"]["w_down"])


def logits(hf: Dict, params: Dict, tokens, precision: str = "f32"):
    """(S,) int tokens -> (S, V) float32 logits.  Departures from the
    published model: the held experts' share of the routed experts (the
    configuration's cut, as the program computes it), and random weights."""
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[0])

    def step(h, lp):
        return _layer(hf, precision, pos, h, lp), None

    for stack in ("dense_layers", "layers"):
        if stack in params:
            x, _ = jax.lax.scan(step, x, params[stack])
    x = _rms(x, params["final_norm"]["scale"], hf["rms_norm_eps"])
    return _mm("sd,dv->sv", x, params["lm_head"], precision)


# -- operations and bytes -----------------------------------------------------


def _attn_matmul_params(hf: Dict) -> int:
    return sum(int(np.prod(s)) for k, s in _attn_shapes(hf).items()
               if not k.endswith("/scale"))


def _expert_params(hf: Dict) -> int:
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def _routed_per_token(hf: Dict) -> float:
    """Held experts a token reaches, at the expectation of uniform
    routing: k · held / E (1.5 for top-6 over 16 of 64)."""
    return (hf["num_experts_per_tok"] * hf["n_routed_experts"]
            / router_width(hf))


def _experts_touched(hf: Dict, n: int) -> float:
    """Held experts that ``n`` tokens reach at least once, uniform
    routing: held · (1 - (1 - k/E)^n)."""
    return hf["n_routed_experts"] * (
        1.0 - (1.0 - hf["num_experts_per_tok"] / router_width(hf)) ** n)


def _moe_fixed_params(hf: Dict) -> int:
    """A MoE layer's matrices that every token uses: router and shared."""
    d = hf["hidden_size"]
    return (d * router_width(hf)
            + 3 * d * hf["moe_intermediate_size"] * hf["n_shared_experts"])


def _dense_layer_params(hf: Dict) -> int:
    return _attn_matmul_params(hf) + 3 * hf["hidden_size"] * \
        hf["intermediate_size"]


def layer_matmul_params(hf: Dict) -> float:
    """Weights of one MoE layer in a token's matrix products: attention,
    router, shared experts and the held routed experts it reaches (at the
    expectation of uniform routing)."""
    return (_attn_matmul_params(hf) + _moe_fixed_params(hf)
            + _routed_per_token(hf) * _expert_params(hf))


def head_params(hf: Dict) -> int:
    return hf["hidden_size"] * hf["vocab_size"]


def _n_dense(hf: Dict) -> int:
    return hf["first_k_dense_replace"]


def _n_moe(hf: Dict) -> int:
    return hf["num_hidden_layers"] - hf["first_k_dense_replace"]


def matmul_params(hf: Dict) -> float:
    """All weights in a token's matrix products: the dense layers, the MoE
    layers (``layer_matmul_params``) and the LM head."""
    return (_n_dense(hf) * _dense_layer_params(hf)
            + _n_moe(hf) * layer_matmul_params(hf) + head_params(hf))


def _norm_params(hf: Dict) -> int:
    n = hf["num_hidden_layers"]
    return (2 * n + 1) * hf["hidden_size"] + n * hf["kv_lora_rank"]


def _fixed_weight_params(hf: Dict) -> int:
    """Every weight but the routed experts'."""
    return (_n_dense(hf) * _dense_layer_params(hf)
            + _n_moe(hf) * (_attn_matmul_params(hf) + _moe_fixed_params(hf))
            + head_params(hf) + _norm_params(hf))


def weight_bytes(hf: Dict, dtype_bytes: int = BF16) -> int:
    """Bytes of every weight held: the layers with all held experts, the
    norms and the LM head (a decode step of many rows reads them all;
    the embedding rows looked up are negligible)."""
    return (_fixed_weight_params(hf) + _n_moe(hf) * hf["n_routed_experts"]
            * _expert_params(hf)) * dtype_bytes


def attn_flops(hf: Dict, s: int) -> int:
    """Forward attention FLOPs of one layer over one sequence, expanded as
    prefill runs it: QK^T over nope + rope dims, PV over the value dims."""
    h = hf["num_attention_heads"]
    dqk = hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]
    return 2 * h * (dqk + hf["v_head_dim"]) * attn_pairs(s)


def prefill_flops(hf: Dict, s: int) -> float:
    """Model FLOPs to prefill one ``s``-token prompt: every layer over
    every position (routed experts at ``_routed_per_token``), and the LM
    head for the last position only."""
    return (2 * s * (matmul_params(hf) - head_params(hf))
            + hf["num_hidden_layers"] * attn_flops(hf, s)
            + 2 * head_params(hf))


def flash_call(hf: Dict, batch: int, s: int) -> Dict[str, float]:
    """Prefill attention has q/k heads of 192 and v heads of 128, so it
    takes the blockwise path, not the flash kernel: no call to count."""
    raise NotImplementedError(
        "deepseek_v2: MLA prefill attention runs no flash-attention kernel")


def gmm_call(hf: Dict, s: int) -> Dict[str, float]:
    """Least FLOPs and bytes of one grouped-matmul call (gate, up or down:
    the three are alike) of one MoE layer over an ``s``-token prompt: the
    routed (token, held expert) rows at the expectation of uniform
    routing, the weights of the held experts they reach once, and each
    row read and written once, bf16."""
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    rows = s * _routed_per_token(hf)
    return {"flops": 2.0 * rows * d * f,
            "bytes": (_experts_touched(hf, s) * d * f + rows * (d + f))
            * BF16}


def gmm_calls_per_prompt(hf: Dict) -> int:
    return 3 * _n_moe(hf)


def decode_bytes(hf: Dict, live_positions: Iterable[int]) -> float:
    """Least bytes of one decode step: every weight but the routed
    experts once, the held experts the step's rows reach (uniform
    routing), each active row's live latent and rope-key rows, and one
    new row per active row, bf16."""
    live = list(live_positions)
    n, r, dr = (hf["num_hidden_layers"], hf["kv_lora_rank"],
                hf["qk_rope_head_dim"])
    total = (_fixed_weight_params(hf) + _n_moe(hf) * _experts_touched(
        hf, len(live)) * _expert_params(hf)) * BF16
    row = n * (r + dr) * BF16
    for p in live:
        total += row * (p + 1)
    return total
