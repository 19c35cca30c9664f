"""Model assembly: init / forward / loss for every architecture family.

Layer stacks are ``lax.scan`` over parameter pytrees stacked on a leading
layer axis — compile time and HLO size are O(1) in depth, which is what
keeps the 512-device dry-run of 96-layer nemotron-340b tractable.

Families:
  dense / vlm      — [frontend] + decoder blocks (GQA or MLA, MLP)
  moe              — decoder blocks with MoE FFN (+ optional shared expert)
  ssm              — Mamba-2 (SSD) blocks
  hybrid           — Mamba-2 backbone, *shared* attention block every
                     ``hybrid_period`` layers (zamba2: 2 alternating sets)
  encdec           — whisper: encoder (bidirectional) + decoder (self+cross)
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.shardctx import constrain, moe_ffn_runner, tp_block_runner

Params = Dict[str, Any]


def _maybe_remat(cfg: ModelConfig, fn):
    """Per-layer activation checkpointing (applied to scan bodies)."""
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _stack_init(init_fn, cfg: ModelConfig, key, n: int) -> Params:
    """vmap an init over layer keys -> pytree with leading layer axis."""
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_fn(cfg, k))(keys)


def _init_attn(cfg: ModelConfig, key) -> Params:
    return (L.init_mla(cfg, key) if cfg.attn_type == "mla"
            else L.init_attention(cfg, key))


def _init_dense_layer(cfg: ModelConfig, key,
                      d_ff: Optional[int] = None) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "ln1": L.init_norm(cfg, k3),
        "attn": _init_attn(cfg, k1),
        "ln2": L.init_norm(cfg, k4),
        "mlp": L.init_mlp(cfg, k2, d_ff=d_ff),
    }


def _init_moe_layer(cfg: ModelConfig, key) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "ln1": L.init_norm(cfg, k3),
        "attn": _init_attn(cfg, k1),
        "ln2": L.init_norm(cfg, k4),
        "moe": L.init_moe(cfg, k2),
    }


def _init_ssm_layer(cfg: ModelConfig, key) -> Params:
    k1, k2 = jax.random.split(key)
    return {"ln": L.init_norm(cfg, k2), "mamba": L.init_mamba2(cfg, k1)}


def _init_encdec_layer(cfg: ModelConfig, key, cross: bool) -> Params:
    ks = jax.random.split(key, 6)
    p = {
        "ln1": L.init_norm(cfg, ks[0]),
        "attn": L.init_attention(cfg, ks[1]),
        "ln2": L.init_norm(cfg, ks[2]),
        "mlp": L.init_mlp(cfg, ks[3]),
    }
    if cross:
        p["ln_x"] = L.init_norm(cfg, ks[4])
        p["xattn"] = L.init_attention(cfg, ks[5])
    return p


def init_params(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 8)
    dt = jnp.dtype(cfg.param_dtype)
    p: Params = {
        "embed": L._init(ks[0], (cfg.vocab_size, cfg.d_model), dt),
        "final_norm": L.init_norm(cfg, ks[1]),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L._init(ks[2], (cfg.d_model, cfg.vocab_size), dt)

    if cfg.family in ("dense", "vlm"):
        p["layers"] = _stack_init(_init_dense_layer, cfg, ks[3], cfg.n_layers)
    elif cfg.family == "moe":
        # the leading dense layers and the MoE layers are two stacks, run
        # one after the other with a running layer index
        if cfg.first_dense_layers:
            p["dense_layers"] = _stack_init(
                lambda c, k: _init_dense_layer(c, k, c.dense_d_ff or None),
                cfg, ks[5], cfg.first_dense_layers)
        p["layers"] = _stack_init(_init_moe_layer, cfg, ks[3],
                                  cfg.n_moe_layers)
    elif cfg.family == "ssm":
        p["layers"] = _stack_init(_init_ssm_layer, cfg, ks[3], cfg.n_layers)
    elif cfg.family == "hybrid":
        p["layers"] = _stack_init(_init_ssm_layer, cfg, ks[3], cfg.n_layers)
        p["shared_blocks"] = _stack_init(
            _init_dense_layer, cfg, ks[4], max(cfg.n_shared_blocks, 1))
    elif cfg.family == "encdec":
        p["enc_layers"] = _stack_init(
            lambda c, k: _init_encdec_layer(c, k, cross=False),
            cfg, ks[3], cfg.n_encoder_layers)
        p["dec_layers"] = _stack_init(
            lambda c, k: _init_encdec_layer(c, k, cross=True),
            cfg, ks[4], cfg.n_layers)
        p["enc_norm"] = L.init_norm(cfg, ks[5])
        p["dec_pos"] = L._init(ks[6], (4096, cfg.d_model), dt, 0.01)
    else:
        raise ValueError(cfg.family)

    if cfg.frontend:
        p["frontend_proj"] = L._init(
            ks[7], (cfg.frontend_dim, cfg.d_model), dt)
    return p


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _dense_block(cfg, p, x, positions):
    runner = tp_block_runner()
    if runner is not None and cfg.use_art and cfg.attn_type != "mla":
        # the paper's technique: every TP collective of this block is an
        # ART ring schedule (models/artblock.py via the step builder)
        return runner(cfg, p, x, positions)
    attn_fn = L.mla_attention if cfg.attn_type == "mla" else L.attention
    a_in = constrain(L.apply_norm(cfg, p["ln1"], x), "block_input")
    h = x + attn_fn(cfg, p["attn"], a_in, positions)
    m_in = constrain(L.apply_norm(cfg, p["ln2"], h), "block_input")
    h = h + L.mlp(cfg, p["mlp"], m_in)
    return h


def _moe_block(cfg, p, x, positions):
    attn_fn = L.mla_attention if cfg.attn_type == "mla" else L.attention
    a_in = constrain(L.apply_norm(cfg, p["ln1"], x), "block_input")
    h = x + attn_fn(cfg, p["attn"], a_in, positions)
    normed = constrain(L.apply_norm(cfg, p["ln2"], h), "block_input")
    ep = moe_ffn_runner()
    if ep is not None:
        # expert-parallel dispatch over the conduit all_to_all
        # (models/moe_ep.py, installed by dist/steps.build_train_step)
        h = h + ep(cfg, p["moe"], normed)
    else:
        h = h + L.moe(cfg, p["moe"], normed)
    aux = L.moe_aux_loss(cfg, normed, p["moe"])
    return h, aux


def _ssm_block(cfg, p, x):
    m_in = constrain(L.apply_norm(cfg, p["ln"], x), "block_input")
    return x + L.mamba2_block(cfg, p["mamba"], m_in)


def _embed(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
           frontend_embeds: Optional[jnp.ndarray]) -> jnp.ndarray:
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.frontend and cfg.family != "encdec":
        assert frontend_embeds is not None, (
            f"{cfg.name} requires precomputed frontend embeddings")
        cd = jnp.dtype(cfg.compute_dtype)
        vis = (frontend_embeds.astype(cd)
               @ params["frontend_proj"].astype(cd)).astype(x.dtype)
        x = jnp.concatenate([vis, x], axis=1)
    return x


def forward_hidden(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,                    # (B, S_text)
    frontend_embeds: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Backbone only: returns (final-norm hidden (B, S, D), moe_aux scalar).
    The LM head is applied by :func:`forward` (tests) or by the *chunked*
    cross-entropy in ``dist/loss.py`` (training — full logits never
    materialize for large-vocab archs)."""
    if cfg.family == "encdec":
        return _forward_encdec_hidden(cfg, params, tokens, frontend_embeds)

    x = constrain(_embed(cfg, params, tokens, frontend_embeds), "residual")
    s = x.shape[1]
    positions = jnp.arange(s)
    aux = jnp.zeros((), jnp.float32)

    if cfg.family in ("dense", "vlm"):
        def body(h, lp):
            h = constrain(_dense_block(cfg, lp, h, positions), "residual")
            return h, None
        x, _ = lax.scan(_maybe_remat(cfg, body), x, params["layers"])
    elif cfg.family == "moe":
        if "dense_layers" in params:
            def lead(h, lp):
                h = constrain(_dense_block(cfg, lp, h, positions), "residual")
                return h, None
            x, _ = lax.scan(_maybe_remat(cfg, lead), x,
                            params["dense_layers"])

        def body(carry, lp):
            h, a = carry
            h, aux_l = _moe_block(cfg, lp, h, positions)
            return (constrain(h, "residual"), a + aux_l), None
        (x, aux), _ = lax.scan(_maybe_remat(cfg, body), (x, aux),
                               params["layers"])
    elif cfg.family == "ssm":
        def body(h, lp):
            return constrain(_ssm_block(cfg, lp, h), "residual"), None
        x, _ = lax.scan(_maybe_remat(cfg, body), x, params["layers"])
    elif cfg.family == "hybrid":
        x = _forward_hybrid(cfg, params, x, positions)
    else:
        raise ValueError(cfg.family)

    x = L.apply_norm(cfg, params["final_norm"], x)
    return constrain(x, "logit_hidden"), aux


def forward(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,                    # (B, S_text)
    frontend_embeds: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits (B, S, V), moe_aux_loss scalar)."""
    x, aux = forward_hidden(cfg, params, tokens, frontend_embeds)
    return _lm_logits(cfg, params, x), aux


def _lm_logits(cfg: ModelConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    cd = jnp.dtype(cfg.compute_dtype)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return jnp.einsum("bsd,dv->bsv", x.astype(cd), head.astype(cd)).astype(
        jnp.float32)


def _forward_hybrid(cfg: ModelConfig, params: Params, x: jnp.ndarray,
                    positions: jnp.ndarray) -> jnp.ndarray:
    """Zamba2: scan groups of ``hybrid_period`` SSM layers, applying one of
    the ``n_shared_blocks`` alternating *shared* attention blocks after each
    group; leftover SSM layers run at the end."""
    period = cfg.hybrid_period
    n_groups = cfg.n_layers // period
    n_rem = cfg.n_layers - n_groups * period
    grouped = jax.tree.map(
        lambda a: a[: n_groups * period].reshape((n_groups, period) + a.shape[1:]),
        params["layers"])
    rest = jax.tree.map(lambda a: a[n_groups * period:], params["layers"])
    shared = params["shared_blocks"]
    n_shared = max(cfg.n_shared_blocks, 1)

    def group_body(carry, inp):
        h, g = carry
        glayers = inp

        def ssm_body(hh, lp):
            return constrain(_ssm_block(cfg, lp, hh), "residual"), None
        h, _ = lax.scan(_maybe_remat(cfg, ssm_body), h, glayers)
        # alternate shared blocks: select block g % n_shared
        sel = jax.tree.map(
            lambda a: a[g % n_shared] if n_shared > 1 else a[0], shared)
        h = constrain(_dense_block(cfg, sel, h, positions), "residual")
        return (h, g + 1), None

    (x, _), _ = lax.scan(_maybe_remat(cfg, group_body), (x, jnp.int32(0)),
                         grouped)
    if n_rem:
        def ssm_body(hh, lp):
            return constrain(_ssm_block(cfg, lp, hh), "residual"), None
        x, _ = lax.scan(_maybe_remat(cfg, ssm_body), x, rest)
    return x


def encode(cfg: ModelConfig, params: Params,
           frontend_embeds: jnp.ndarray) -> jnp.ndarray:
    """Whisper encoder: precomputed frame embeddings (stub frontend) ->
    encoder output (B, S_enc, D)."""
    cd = jnp.dtype(cfg.compute_dtype)
    enc = (frontend_embeds.astype(cd)
           @ params["frontend_proj"].astype(cd))
    enc = enc + L.sinusoidal_positions(enc.shape[1], cfg.d_model).astype(cd)
    enc = enc.astype(jnp.dtype(cfg.param_dtype))
    positions = jnp.arange(enc.shape[1])

    def enc_body(h, lp):
        hh = h + L.attention(cfg, lp["attn"], L.apply_norm(cfg, lp["ln1"], h),
                             positions, causal=False)
        hh = hh + L.mlp(cfg, lp["mlp"], L.apply_norm(cfg, lp["ln2"], hh))
        return constrain(hh, "residual"), None

    enc, _ = lax.scan(_maybe_remat(cfg, enc_body), enc, params["enc_layers"])
    return L.apply_norm(cfg, params["enc_norm"], enc)


def _forward_encdec_hidden(cfg: ModelConfig, params: Params,
                           tokens: jnp.ndarray,
                           frontend_embeds: Optional[jnp.ndarray]):
    """Whisper backbone: frame embeddings (stub frontend) -> encoder;
    token embeddings + learned positions -> decoder with cross-attention."""
    assert frontend_embeds is not None, "whisper needs precomputed frames"
    enc = encode(cfg, params, frontend_embeds)

    x = jnp.take(params["embed"], tokens, axis=0)
    s = x.shape[1]
    pos_table = params["dec_pos"]
    x = x + lax.dynamic_slice_in_dim(pos_table, 0, s, 0).astype(x.dtype)
    dpos = jnp.arange(s)

    def dec_body(h, lp):
        hh = h + L.attention(cfg, lp["attn"], L.apply_norm(cfg, lp["ln1"], h),
                             dpos, causal=True)
        kv = L.cross_kv(cfg, lp["xattn"], enc)
        hh = hh + L.attention(cfg, lp["xattn"],
                              L.apply_norm(cfg, lp["ln_x"], hh),
                              dpos, causal=False, kv_override=kv)
        hh = hh + L.mlp(cfg, lp["mlp"], L.apply_norm(cfg, lp["ln2"], hh))
        return constrain(hh, "residual"), None

    x, _ = lax.scan(_maybe_remat(cfg, dec_body), x, params["dec_layers"])
    x = L.apply_norm(cfg, params["final_norm"], x)
    return constrain(x, "logit_hidden"), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def loss_fn(
    cfg: ModelConfig,
    params: Params,
    batch: Dict[str, jnp.ndarray],
    *,
    z_loss: float = 1e-4,
    moe_aux_weight: float = 1e-2,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """batch: tokens (B,S), labels (B,S) with -1 = masked, plus optional
    frontend_embeds.  For vlm, logits over image positions are dropped."""
    logits, aux = forward(cfg, params, batch["tokens"],
                          batch.get("frontend_embeds"))
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:       # vlm: crop frontend positions
        logits = logits[:, logits.shape[1] - labels.shape[1]:]
    mask = (labels >= 0).astype(jnp.float32)
    labels_safe = jnp.maximum(labels, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels_safe[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = nll.sum() / denom
    zl = z_loss * ((lse * mask) ** 2).sum() / denom
    total = ce + zl + moe_aux_weight * aux
    return total, {"ce": ce, "z_loss": zl, "moe_aux": aux,
                   "tokens": mask.sum()}


# ---------------------------------------------------------------------------
# analytic parameter counts (validates init + feeds MODEL_FLOPS)
# ---------------------------------------------------------------------------


def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    hd = cfg.resolved_head_dim if cfg.n_heads else 0
    norm = 2 * d if cfg.family == "encdec" else d  # LayerNorm has a bias

    def attn_params():
        if cfg.attn_type == "mla":
            h = cfg.n_heads
            q_out = h * (cfg.qk_nope_dim + cfg.qk_rope_dim)
            q = (d * cfg.q_lora_rank + cfg.q_lora_rank
                 + cfg.q_lora_rank * q_out) if cfg.q_lora_rank else d * q_out
            return (q + d * (cfg.kv_lora_rank + cfg.qk_rope_dim) + cfg.kv_lora_rank
                    + cfg.kv_lora_rank * h * cfg.qk_nope_dim
                    + cfg.kv_lora_rank * h * cfg.v_head_dim
                    + h * cfg.v_head_dim * d)
        return d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd \
            + cfg.n_heads * hd * d

    def mlp_params(ff=None):
        ff = ff or f
        return (3 if cfg.gated_mlp else 2) * d * ff

    def moe_params():
        n_e = (cfg.experts_per_token if active_only
               else cfg.n_local_experts)
        total = d * cfg.n_experts  # router (always resident)
        total += n_e * (3 if cfg.gated_mlp else 2) * d * f
        if cfg.n_shared_experts:
            total += mlp_params(f * cfg.n_shared_experts)
        return total

    def ssm_params():
        d_in = cfg.ssm_heads * cfg.ssm_head_dim
        conv_ch = d_in + 2 * cfg.ssm_groups * cfg.ssm_state
        proj_out = 2 * d_in + 2 * cfg.ssm_groups * cfg.ssm_state + cfg.ssm_heads
        return (d * proj_out + cfg.ssm_conv * conv_ch + conv_ch
                + 3 * cfg.ssm_heads + d_in + d_in * d + d)  # + ln scale

    total = v * d + norm  # embed + final_norm
    if not cfg.tie_embeddings:
        total += d * v
    if cfg.frontend:
        total += cfg.frontend_dim * d

    if cfg.family in ("dense", "vlm"):
        total += cfg.n_layers * (attn_params() + mlp_params() + 2 * d)
    elif cfg.family == "moe":
        total += cfg.first_dense_layers * (
            attn_params() + mlp_params(cfg.dense_d_ff) + 2 * d)
        total += cfg.n_moe_layers * (attn_params() + moe_params() + 2 * d)
    elif cfg.family == "ssm":
        total += cfg.n_layers * ssm_params()
    elif cfg.family == "hybrid":
        total += cfg.n_layers * ssm_params()
        total += max(cfg.n_shared_blocks, 1) * (
            attn_params() + mlp_params() + 2 * d)
    elif cfg.family == "encdec":
        per_enc = attn_params() + mlp_params() + 2 * norm
        per_dec = 2 * attn_params() + mlp_params() + 3 * norm
        total += cfg.n_encoder_layers * per_enc + cfg.n_layers * per_dec
        total += norm + 4096 * d  # enc_norm + learned decoder positions
    return int(total)
