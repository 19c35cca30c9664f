"""ModelConfig — one dataclass describing every assigned architecture.

Families: dense / moe / ssm / hybrid / encdec / vlm.  A config fully
determines parameter shapes, the forward pass, cache layout, and the
sharding rules; ``reduced()`` produces the small same-family variant used by
the per-arch CPU smoke tests (the full configs are only ever lowered via the
dry-run with ShapeDtypeStructs — no allocation).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: Optional[int] = None  # default: d_model // n_heads

    # -- attention variant ---------------------------------------------------
    attn_type: str = "gqa"          # gqa | mla | none
    window: Optional[int] = None    # sliding-window attention (h2o-danube)
    # MLA (minicpm3, deepseek-v2-lite); q_lora_rank 0 = a direct w_q
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    qk_nope_dim: int = 0
    v_head_dim: int = 0

    # -- MLP -------------------------------------------------------------------
    activation: str = "silu"        # silu | gelu | relu2
    gated_mlp: bool = True

    # -- MoE -------------------------------------------------------------------
    n_experts: int = 0              # the router's outputs
    experts_per_token: int = 0
    n_shared_experts: int = 0
    moe_layer_period: int = 1       # 1 => every layer is MoE
    capacity_factor: Optional[float] = 1.25   # None: dropless routing
    norm_topk_prob: bool = True     # renormalise the top-k weights to 1
    # the experts this chip holds: experts [expert_offset, +experts_held)
    # of the router's n_experts (0 = all; the expert-parallel share)
    experts_held: int = 0
    expert_offset: int = 0
    # leading dense layers of a MoE stack (deepseek first_k_dense_replace)
    # and their MLP width (0 = d_ff, the expert width)
    first_dense_layers: int = 0
    dense_d_ff: int = 0

    # -- SSM (mamba2 / zamba2) --------------------------------------------------
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_chunk: int = 128
    ssm_stream_segments: int = 0    # >1: chunk-fed SSD scan (segments streamed
                                    # into the kernel, state carried — the
                                    # fused consume-in-pipeline discipline)
    hybrid_period: int = 0          # zamba2: shared attn block every k ssm layers
    n_shared_blocks: int = 0        # zamba2: number of alternating shared blocks

    # -- encoder-decoder (whisper) ----------------------------------------------
    n_encoder_layers: int = 0
    encoder_seq: int = 0            # whisper: 1500 frames
    decoder_max_seq: int = 448

    # -- modality frontend (stub per task spec) ---------------------------------
    frontend: Optional[str] = None  # vit_stub | audio_stub
    frontend_tokens: int = 0        # precomputed patch/frame embeddings count
    frontend_dim: int = 0

    # -- common ------------------------------------------------------------------
    rope_theta: float = 10000.0
    # YaRN rope scaling as sorted (key, value) pairs of the published
    # ``rope_scaling`` (factor, original_max_position_embeddings,
    # beta_fast, beta_slow, mscale, mscale_all_dim); None = plain rope
    rope_scaling: Optional[Tuple[Tuple[str, float], ...]] = None
    # rotate (2i, 2i+1) pairs rather than halves (deepseek's de-interleave)
    rope_interleave: bool = False
    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    # -- numerics / implementation ------------------------------------------------
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    attn_impl: str = "auto"         # auto | pallas | jnp | ref
    attn_q_chunk: int = 2048
    attn_kv_chunk: int = 2048
    causal_block_skip: bool = True  # skip fully-masked kv blocks (perf lever)
    remat: str = "full"             # none | dots | full (activation ckpt policy)

    # -- the paper's technique ------------------------------------------------------
    use_art: bool = True            # ART-chunked/overlapped TP collectives
    art_chunks: int = 4             # chunk count for overlapped schedules

    # ---------------------------------------------------------------------------

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic sequence scaling: SSM/hybrid state or SWA window."""
        return self.family in ("ssm", "hybrid") or self.window is not None

    @property
    def n_local_experts(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.experts_held or self.n_experts

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_dense_layers

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    def n_params(self) -> int:
        """Total parameter count (exact, mirrors init_params)."""
        from repro.models.model import count_params_analytic

        return count_params_analytic(self)

    def n_active_params(self) -> int:
        """Parameters touched per token (MoE: routed top-k + shared only)."""
        from repro.models.model import count_params_analytic

        return count_params_analytic(self, active_only=True)

    def reduced(self) -> "ModelConfig":
        """Same-family miniature for CPU smoke tests."""
        r = {
            "n_layers": min(self.n_layers, 2),
            "d_model": 64,
            "n_heads": 4,
            "n_kv_heads": min(self.n_kv_heads, 2) if self.n_kv_heads else 0,
            "d_ff": 128,
            "vocab_size": 256,
            "head_dim": 16,
            "param_dtype": "float32",
            "compute_dtype": "float32",
            "attn_impl": "jnp",
            "attn_q_chunk": 16,
            "attn_kv_chunk": 16,
            "remat": "none",
        }
        if self.attn_type == "mla":
            r.update(q_lora_rank=32 if self.q_lora_rank else 0,
                     kv_lora_rank=16, qk_rope_dim=8, qk_nope_dim=8,
                     v_head_dim=16, head_dim=16)
        if self.window is not None:
            r["window"] = 8
        if self.n_experts:
            r.update(n_experts=4, experts_per_token=min(self.experts_per_token, 2))
            if self.n_experts > 16:
                # a wide router keeps 8 outputs, of which the miniature
                # holds 4: the share one chip of an expert-parallel
                # deployment holds
                r.update(n_experts=8, experts_held=4, expert_offset=0)
        if self.first_dense_layers:
            r.update(n_layers=self.first_dense_layers + 2, dense_d_ff=192)
        if self.family in ("ssm", "hybrid"):
            r.update(ssm_state=16, ssm_heads=4, ssm_head_dim=16, ssm_chunk=8,
                     ssm_groups=1)
            if self.family == "hybrid":
                r.update(n_layers=5, hybrid_period=2,
                         n_shared_blocks=min(self.n_shared_blocks, 2))
        if self.family == "encdec":
            r.update(n_encoder_layers=2, encoder_seq=16, decoder_max_seq=32)
        if self.frontend:
            # encdec frontends feed the encoder: the frame count must equal
            # encoder_seq so the prefill cross-cache extent matches
            # decode.init_cache's (which sizes it from encoder_seq)
            r.update(frontend_tokens=16 if self.family == "encdec" else 8,
                     frontend_dim=32)
        return dataclasses.replace(self, **r)


# ---------------------------------------------------------------------------
# serving capability table (jax-free — tools/docs_check.py imports this)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ChunkCarrySpec:
    """The per-architecture chunk-carry contract of streamed prefill.

    What a prefill chunk hands to the next one (``models/prefill.py``
    implements the matching ``init_prefill_scratch`` / ``prefill_chunk`` /
    ``scratch_to_cache`` triple per ``kind``):

    * ``ring`` — full-length K/V scratch rows (GQA dense / vlm / moe);
    * ``latent`` — full-length MLA latent (``ckv``) + shared rope key rows;
    * ``state`` — **constant-size** SSD state + conv tail (mamba2), riding
      the ``ssd`` kernel's ``init_state`` resume hook;
    * ``hybrid`` — the ``state`` pair per layer plus ring rows for the
      shared attention blocks (zamba2);
    * ``encdec`` — encoder output turned into cross-K/V once (chunk 0),
      then decoder ring rows (whisper).

    ``exact`` is the bit-identity claim: chunked ≡ bulk prefill, bit for
    bit.  MoE with a capacity is the one documented exception
    (``exact=False``; dropless routing, ``capacity_factor=None``, is
    exact): expert capacity is bookkept per chunk, so the drop set may differ from bulk's
    — each MoE layer's output agrees bitwise at every token whose
    per-(token, expert) keep decisions match, and the whole forward is
    exact when they match everywhere, in particular when no row overflows
    either program (``models/prefill.moe_chunk_agree_mask`` states the
    bound; the zoo suite asserts it).

    ``chunk_multiple``: interior chunk cuts must land on multiples of this
    (the SSD chunk walk of ``ssm_chunk``-sized blocks must line up with
    bulk's for the state hand-off to be bit-exact); the server rounds its
    ``prefill_chunk`` up to it.
    """

    kind: str              # ring | latent | state | hybrid | encdec
    constant_size: bool    # carry size independent of the prompt length
    exact: bool            # chunked ≡ bulk bit-identical
    chunk_multiple: int    # interior cuts land on multiples of this
    note: str = ""


def chunk_carry_spec(cfg: ModelConfig) -> ChunkCarrySpec:
    """The chunk-carry contract of ``cfg`` — total over the config zoo."""
    if cfg.family == "ssm":
        return ChunkCarrySpec(
            "state", constant_size=True, exact=True,
            chunk_multiple=max(1, cfg.ssm_chunk),
            note="constant SSD state + conv tail per layer")
    if cfg.family == "hybrid":
        return ChunkCarrySpec(
            "hybrid", constant_size=False, exact=True,
            chunk_multiple=max(1, cfg.ssm_chunk),
            note="SSD state pair + shared-attention ring rows")
    if cfg.family == "encdec":
        return ChunkCarrySpec(
            "encdec", constant_size=False, exact=True, chunk_multiple=1,
            note="cross-K/V once at chunk 0, decoder ring rows after")
    capacity_moe = cfg.family == "moe" and cfg.capacity_factor is not None
    if cfg.attn_type == "mla":
        if capacity_moe:
            return ChunkCarrySpec(
                "latent", constant_size=False, exact=False,
                chunk_multiple=1,
                note="latent rows; chunk-local expert capacity")
        return ChunkCarrySpec(
            "latent", constant_size=False, exact=True, chunk_multiple=1,
            note="latent ckv + shared rope key rows"
                 + ("; dropless experts" if cfg.family == "moe" else ""))
    if capacity_moe:
        return ChunkCarrySpec(
            "ring", constant_size=False, exact=False, chunk_multiple=1,
            note="chunk-local expert capacity — exact iff no row drops")
    return ChunkCarrySpec("ring", constant_size=False, exact=True,
                          chunk_multiple=1, note="K/V ring rows")


def serving_features(cfg: ModelConfig) -> "dict[str, bool]":
    """Arch × serving-feature support row (the docs/serving.md matrix).

    ``chunked``: the chunk-carry contract exists (it is total — every arch
    chunks; the *runtime* gate ``models/prefill.chunk_support`` may still
    fall back to bulk when the resolved attention impl lacks the
    mid-sequence ``q_offset`` convention, with a build warning).
    ``chunked_exact``: the bit-identity claim of :func:`chunk_carry_spec`.
    ``paged`` / ``prefix_cache``: the paged KV block pool and its
    prompt-prefix sharing (ring K/V caches only; sharing additionally
    needs position-stable slots — no SWA wrap — and byte-keyable prompts,
    which frontend embeddings are not).  ``ep_decode``: expert-parallel
    decode dispatch over the conduit (``models/moe_ep.py``), which keeps
    the capacity bookkeeping of the routing: a dropless layer decodes
    with dense combine over its held experts instead.
    """
    spec = chunk_carry_spec(cfg)
    paged = (cfg.family in ("dense", "vlm", "moe")
             and cfg.attn_type != "mla")
    return {
        "chunked": True,
        "chunked_exact": spec.exact,
        "paged": paged,
        "prefix_cache": (paged and cfg.window is None
                         and not cfg.frontend),
        "ep_decode": cfg.family == "moe" and cfg.capacity_factor is not None,
    }


# Input-shape cells assigned to every LM arch (task spec).
@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: Tuple[ShapeCell, ...] = (
    ShapeCell("train_4k", 4_096, 256, "train"),
    ShapeCell("prefill_32k", 32_768, 32, "prefill"),
    ShapeCell("decode_32k", 32_768, 128, "decode"),
    ShapeCell("long_500k", 524_288, 1, "decode"),
)


def shape_cell(name: str) -> ShapeCell:
    for s in SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)


def cell_applicable(cfg: ModelConfig, cell: ShapeCell) -> Tuple[bool, str]:
    """Whether (arch × shape) runs, with the DESIGN.md skip reason if not."""
    if cell.name == "long_500k" and not cfg.supports_long_context:
        return False, "full quadratic attention — long_500k skipped (DESIGN §5)"
    if cell.name == "long_500k" and cfg.family == "encdec":
        return False, "enc-dec decoder context 448 — long_500k skipped"
    return True, ""
