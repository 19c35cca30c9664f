"""Step builders: jit-compiled, mesh-sharded train / prefill / serve steps.

This is the layer that turns the mesh-agnostic model zoo into distributed
programs.  Each ``build_*`` returns a :class:`StepBundle`: a jitted callable
with in/out shardings bound, the matching PartitionSpec trees (so runtimes
can place state without re-deriving rules), and shape templates for
checkpoint restore and dry-run lowering.

Responsibilities:

  * sharding — parameter/optimizer/cache/batch placement from
    ``dist/sharding.py``; activation constraints are injected into the
    model's ``constrain(x, tag)`` call sites via ``models/shardctx.py``
    (sequence-parallel residual when the length divides TP);
  * loss — the sequence-chunked CE from ``dist/loss.py`` (full logits never
    materialize at train shapes);
  * microbatching — ``lax.scan`` gradient accumulation in fp32; with equal
    per-microbatch token counts the update is exactly the full-batch one
    (asserted by ``tests/test_dist.py::test_microbatch_equivalence``);
  * transport selection — :class:`TransportPolicy` names a conduit
    transport per traffic class (TP collectives of dense blocks, MoE
    dispatch, cross-pod gradients).  A non-``xla`` ``tp`` transport swaps
    every TP collective of dense blocks for the conduit-scheduled PGAS
    rings of ``models/artblock.py`` (the paper's ART as a training
    feature); the legacy boolean ``StepConfig.art_tp`` still works through
    a deprecation shim.  A non-``xla`` ``moe`` transport swaps the dense
    GSPMD MoE layer for the expert-parallel bucketed all_to_all dispatch
    of ``models/moe_ep.py`` whenever the mesh has an ``expert`` axis
    (falls back to dense otherwise — same numerics).  The cross-pod
    gradient hop has its own PGAS conduit in ``dist/grad_sync.py``
    (operating on per-pod gradients, pod-sharded layout); wiring it
    *inside* this GSPMD step would require partial-manual shard_map over
    ``pod``, which the SPMD partitioner rejected — see DESIGN §6 and the
    ROADMAP open item.

See ``docs/api.md`` for the public surface and ``docs/transports.md`` for
the op × transport support matrix these policies select from.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, serving_features
from repro.core.conduit import Conduit, transports as conduit_transports
from repro.dist import bucketing
from repro.dist.loss import chunked_ce_loss
from repro.dist.sharding import (
    MeshAxes,
    batch_pspecs,
    cache_pspecs,
    dp_axes,
    fit_axis,
    opt_pspecs,
    param_pspecs,
    to_shardings,
)
from repro.models import artblock
from repro.models import layers as L
from repro.models import moe_ep
from repro.models.decode import (
    decode_step,
    init_cache,
    init_paged_cache,
    paged_slot_blocks,
)
from repro.models.model import init_params
from repro.models.prefill import (
    chunk_support,
    init_prefill_scratch,
    prefill,
    prefill_chunk,
    prefill_chunked,
    supports_chunked_prefill,
)
from repro.models.shardctx import activation_sharding
from repro.optim import (
    AdamWConfig,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    warmup_cosine,
)


@dataclasses.dataclass(frozen=True)
class TransportPolicy:
    """Conduit transport per traffic class (DESIGN §6, docs/transports.md).

    Each field names a transport registered in ``repro.core.conduit``
    (``xla`` | ``ring`` | ``bidir`` | ``auto``).  ``xla`` means "leave the
    collective to the GSPMD partitioner" — no manual region is built.

    ``tp``         — TP collectives of dense blocks (QKV/O, up/down rings):
                     any ring family routes them through the ART schedules
                     of ``models/artblock.py`` over a ``Conduit("model")``;
                     ``fused`` pins the in-kernel Pallas collective
                     matmuls (``kernels/cc_matmul``) at those edges;
    ``moe``        — MoE expert dispatch: any non-``xla`` value routes
                     token buckets through the conduit ``all_to_all`` on
                     the ``expert`` mesh axis (``models/moe_ep.py``);
                     meshes without an ``expert`` axis keep the dense
                     GSPMD capacity einsums regardless of this field;
    ``cross_pod``  — the DCN gradient hop (``dist/grad_sync.py``);
    ``compress_cross_pod`` — wrap the cross-pod conduit in EF-int8
                     (``grad_sync.Int8Conduit``);
    ``chunk_bytes`` — ART chunk size handed to every conduit (None: let
                     ``auto`` pick / transport default);
    ``moe_stream_chunks`` — stream the EP dispatch: split each MoE
                     exchange into this many ART chunks so expert compute
                     on bucket *k−1* overlaps bucket *k*'s ``all_to_all``
                     (``models/moe_ep.py``; bit-identical to the bulk
                     exchange; None/1 keeps bulk).
    """

    tp: str = "xla"
    moe: str = "xla"
    cross_pod: str = "ring"
    compress_cross_pod: bool = False
    chunk_bytes: Optional[int] = None
    moe_stream_chunks: Optional[int] = None

    def __post_init__(self):
        # each traffic class validates against the registry of the op it
        # actually rides (tp gathers/scatters, moe dispatches,
        # cross_pod reduces)
        for cls, op in (("tp", "all_gather"), ("moe", "all_to_all"),
                        ("cross_pod", "all_reduce")):
            name = getattr(self, cls)
            valid = ("auto",) + conduit_transports(op)
            if name not in valid:
                raise ValueError(
                    f"TransportPolicy.{cls}={name!r} not in {valid}")

    def tp_conduit(self, axis: str = "model") -> Conduit:
        """The conduit handle the ART-TP schedules run over."""
        return Conduit(axis=axis, transport=self.tp,
                       chunk_bytes=self.chunk_bytes)


@dataclasses.dataclass(frozen=True)
class StepConfig:
    """Per-run knobs of the distributed step (model config stays pure)."""

    microbatches: int = 1
    seq_chunk: int = 512             # CE streaming chunk (dist/loss.py)
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    moment_dtype: str = "float32"    # "bfloat16" for >=100B archs
    master_fp32: bool = True
    sequence_parallel: bool = True   # shard S of the residual over TP
    art_tp: bool = False             # DEPRECATED: use transport=TransportPolicy
    transport: Optional[TransportPolicy] = None
    # microbatch grads accumulate into size-targeted flat buckets
    # (dist/bucketing.py) instead of the leaf pytree: each bucket's add for
    # microbatch k is independent of microbatch k+1's backward, and the
    # bucket layout is what a bucketed conduit sync ships.  None: pytree
    # accumulation (bit-identical either way — asserted in tests).
    grad_bucket_bytes: Optional[int] = None
    z_loss: float = 1e-4
    moe_aux_weight: float = 1e-2

    def resolved_transport(self) -> TransportPolicy:
        """The effective policy, honoring the deprecated ``art_tp`` flag.

        ``art_tp=True`` historically meant "bidirectional PGAS rings for
        every TP collective of dense blocks" — it maps to
        ``TransportPolicy(tp="bidir")``."""
        if self.transport is not None:
            return self.transport
        if self.art_tp:
            warnings.warn(
                "StepConfig.art_tp is deprecated; use "
                "StepConfig(transport=TransportPolicy(tp='bidir'))",
                DeprecationWarning, stacklevel=2)
            return TransportPolicy(tp="bidir")
        return TransportPolicy()


def refit_step_config(scfg: StepConfig, old_data: int,
                      new_data: int) -> StepConfig:
    """Re-fit a :class:`StepConfig` after the data axis changed size.

    Elastic membership changes (``runtime/elastic.py``) keep two
    invariants whether the data axis shrank (rank loss) or grew
    (scale-out join):

    * **global batch constant** — ``microbatches`` scales by
      ``old_data // new_data`` on a shrink (each survivor accumulates the
      shards the dead rank used to hold) and *divides* by
      ``new_data // old_data`` on a growth (the joiner takes shards
      back); either direction must divide cleanly, which
      :func:`repro.runtime.elastic.viable_mesh_shapes` guarantees for
      shrinks and the join admission checks for growths;
    * **per-hop ring message constant** — ``grad_bucket_bytes`` (when
      set) scales by ``new_data / old_data`` via
      :func:`repro.dist.bucketing.span_scaled_target`, since a ring
      all-reduce puts ``target/span`` bytes on each hop.
    """
    if old_data < 1 or new_data < 1:
        raise ValueError(f"data spans must be >= 1 ({old_data} -> {new_data})")
    if old_data % new_data == 0:
        micro = scfg.microbatches * (old_data // new_data)
    elif new_data % old_data == 0:
        factor = new_data // old_data
        if scfg.microbatches % factor != 0:
            raise RuntimeError(
                f"cannot hold global batch: {scfg.microbatches} microbatches "
                f"do not split over growth {old_data} -> {new_data}")
        micro = scfg.microbatches // factor
    else:
        raise RuntimeError(
            f"cannot hold global batch: data axis {old_data} -> {new_data} "
            f"is not a clean shrink or growth")
    changes: Dict[str, Any] = {"microbatches": micro}
    if scfg.grad_bucket_bytes is not None:
        changes["grad_bucket_bytes"] = bucketing.span_scaled_target(
            scfg.grad_bucket_bytes, old_data, new_data)
    return dataclasses.replace(scfg, **changes)


@dataclasses.dataclass
class StepBundle:
    """A built step: jitted fn + the specs/shapes runtimes need around it."""

    fn: Any                          # jitted callable (has .lower)
    in_specs: Tuple[Any, ...]        # PartitionSpec tree per positional arg
    out_specs: Any
    aux: Dict[str, Any]              # params_shape / opt_shape / cache_shape


# ---------------------------------------------------------------------------
# shared plumbing
# ---------------------------------------------------------------------------


def _adamw_config(scfg: StepConfig) -> AdamWConfig:
    return AdamWConfig(lr=scfg.peak_lr, weight_decay=scfg.weight_decay,
                       moment_dtype=scfg.moment_dtype,
                       master_fp32=scfg.master_fp32)


def _state_shapes(cfg: ModelConfig, scfg: StepConfig):
    params_shape = jax.eval_shape(functools.partial(init_params, cfg),
                                  jax.random.PRNGKey(0))
    opt_shape = jax.eval_shape(
        functools.partial(adamw_init, cfg=_adamw_config(scfg)), params_shape)
    return params_shape, opt_shape


def _tp_extent(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1


def _constraint_fn(cfg: ModelConfig, mesh, scfg: StepConfig) -> Callable:
    """The ``constrain(x, tag)`` implementation installed for a trace."""
    dp = dp_axes(mesh)
    tp = "model" if "model" in mesh.axis_names else None
    tp_n = _tp_extent(mesh)

    def constrain(x, tag: str):
        if getattr(x, "ndim", 0) != 3:
            return x
        if tag in ("residual", "block_input"):
            sp = (scfg.sequence_parallel and tp is not None
                  and x.shape[1] % tp_n == 0)
            spec = P(dp, tp if sp else None, None)
        elif tag == "logit_hidden":
            spec = P(dp, None, None)
        else:
            return x
        return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return constrain


def _scalar_sharding(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# ART-TP block runner (the paper's transport inside the train step)
# ---------------------------------------------------------------------------


def _art_runner(cfg: ModelConfig, mesh,
                policy: TransportPolicy) -> Optional[Callable]:
    """Dense-block runner with every TP collective a PGAS conduit schedule.

    Norms and the (small) K/V projections stay GSPMD; the two manual regions
    differentiate only tp-sharded tensors (see models/artblock.py notes).
    Returns None when ``policy.tp`` leaves TP to GSPMD (``xla``) or the
    arch/mesh cannot take the manual schedule — the step then falls back to
    GSPMD collectives, same numerics.
    """
    tp_n = _tp_extent(mesh)
    if policy.tp == "xla" or tp_n <= 1 \
            or not artblock.supports_art_tp(cfg, tp_n):
        return None
    conduit = policy.tp_conduit("model")
    dp = dp_axes(mesh)
    act3 = P(dp, "model", None)
    cd = jnp.dtype(cfg.compute_dtype)

    def runner(cfg_, lp, x, positions):
        attn_p, mlp_p = lp["attn"], lp["mlp"]
        a_in = L.apply_norm(cfg_, lp["ln1"], x)
        k_full = jnp.einsum("bsd,dh->bsh", a_in.astype(cd),
                            attn_p["wk"].astype(cd))
        v_full = jnp.einsum("bsd,dh->bsh", a_in.astype(cd),
                            attn_p["wv"].astype(cd))

        attn_fn = jax.shard_map(
            functools.partial(artblock.art_attention_part, cfg_,
                              conduit=conduit),
            mesh=mesh,
            in_specs=(act3, act3, act3, act3,
                      P(None, "model"), P("model", None), P(None)),
            out_specs=act3, check_vma=False)
        h = attn_fn(x, a_in, k_full, v_full, attn_p["wq"], attn_p["wo"],
                    positions)

        m_in = L.apply_norm(cfg_, lp["ln2"], h)
        w_gate = mlp_p.get("w_gate")
        if w_gate is not None:
            def gated(h_, m_, wu, wg, wd):
                return artblock.art_mlp_part(cfg_, h_, m_, wu, wg, wd,
                                             conduit=conduit)
            mlp_fn = jax.shard_map(
                gated, mesh=mesh,
                in_specs=(act3, act3, P(None, "model"), P(None, "model"),
                          P("model", None)),
                out_specs=act3, check_vma=False)
            return mlp_fn(h, m_in, mlp_p["w_up"], w_gate, mlp_p["w_down"])

        def ungated(h_, m_, wu, wd):
            return artblock.art_mlp_part(cfg_, h_, m_, wu, None, wd,
                                         conduit=conduit)
        mlp_fn = jax.shard_map(
            ungated, mesh=mesh,
            in_specs=(act3, act3, P(None, "model"), P("model", None)),
            out_specs=act3, check_vma=False)
        return mlp_fn(h, m_in, mlp_p["w_up"], mlp_p["w_down"])

    return runner


# ---------------------------------------------------------------------------
# Pallas attention runner (the kernel inside a shard_map)
# ---------------------------------------------------------------------------


def _attention_runner(cfg: ModelConfig, mesh) -> Optional[Callable]:
    """Flash-attention runner for meshes of more than one device.

    GSPMD cannot partition a Mosaic kernel, so the Pallas call runs inside
    a ``shard_map`` whose specs shard batch over the data axes and heads
    over ``model`` wherever they divide (both q and kv heads must, so each
    shard keeps whole GQA groups); axes that divide nothing replicate.
    Returns None on one device, or when attention does not resolve to the
    kernel — the model then calls it (or the jnp path) directly.
    """
    if mesh.size == 1 or L.resolve_attn_impl(cfg) != "pallas":
        return None
    from repro.kernels.flash_attention import flash_attention

    dp = dp_axes(mesh)
    tp = "model" if "model" in mesh.axis_names else None

    def runner(q, k, v, **kw):
        heads = fit_axis(mesh, tp, q.shape[1])
        if fit_axis(mesh, tp, k.shape[1]) is None:
            heads = None
        spec = P(fit_axis(mesh, dp, q.shape[0]), heads, None, None)
        return jax.shard_map(
            functools.partial(flash_attention, **kw), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)

    return runner


# ---------------------------------------------------------------------------
# expert-parallel MoE runner (conduit all_to_all dispatch)
# ---------------------------------------------------------------------------


def _moe_runner(cfg: ModelConfig, mesh,
                policy: TransportPolicy) -> Optional[Callable]:
    """MoE-layer runner with expert dispatch on the conduit ``all_to_all``.

    ``policy.moe="xla"`` (or a mesh without a usable ``expert`` axis)
    returns None — the step keeps the dense GSPMD capacity einsums, same
    numerics; so does a dropless MoE (``capacity_factor=None``), which the
    capacity-bookkept exchange does not implement.  Otherwise tokens ride
    the bucketed exchange of ``models/moe_ep.py`` over
    ``Conduit("expert", policy.moe)``.
    """
    if (policy.moe == "xla" or cfg.family != "moe"
            or cfg.capacity_factor is None):
        return None
    return moe_ep.build_moe_ep_runner(
        cfg, mesh, transport=policy.moe, chunk_bytes=policy.chunk_bytes,
        stream_chunks=policy.moe_stream_chunks)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def build_init(cfg: ModelConfig, mesh, scfg: StepConfig):
    """Returns ``(init_fn, (param_pspecs, opt_pspecs))``; ``init_fn(key)``
    materializes sharded (params, opt_state) directly on the mesh."""
    params_shape, opt_shape = _state_shapes(cfg, scfg)
    pspecs = param_pspecs(cfg, mesh, params_shape)
    ospecs = opt_pspecs(cfg, mesh, opt_shape, pspecs)
    acfg = _adamw_config(scfg)

    @functools.partial(
        jax.jit,
        out_shardings=(to_shardings(mesh, pspecs), to_shardings(mesh, ospecs)))
    def init_fn(key):
        params = init_params(cfg, key)
        return params, adamw_init(params, acfg)

    return init_fn, (pspecs, ospecs)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def build_train_step(cfg: ModelConfig, mesh, scfg: StepConfig,
                     bshape) -> StepBundle:
    """``fn(params, opt, batch, step) -> (params, opt, metrics)``.

    ``params`` and ``opt`` are **donated**: their buffers are reused for
    the outputs, so a caller must not read them after the call."""
    params_shape, opt_shape = _state_shapes(cfg, scfg)
    pspecs = param_pspecs(cfg, mesh, params_shape)
    ospecs = opt_pspecs(cfg, mesh, opt_shape, pspecs)
    bspecs = batch_pspecs(mesh, bshape)
    acfg = _adamw_config(scfg)
    constrain = _constraint_fn(cfg, mesh, scfg)
    policy = scfg.resolved_transport()
    runner = _art_runner(cfg, mesh, policy)
    moe_runner = _moe_runner(cfg, mesh, policy)
    attn_runner = _attention_runner(cfg, mesh)
    n_micro = max(int(scfg.microbatches), 1)

    def loss_fn(params, microbatch):
        with activation_sharding(constrain, tp_block=runner,
                                 moe_ffn=moe_runner, attention=attn_runner):
            return chunked_ce_loss(
                cfg, params, microbatch, seq_chunk=scfg.seq_chunk,
                z_loss=scfg.z_loss, moe_aux_weight=scfg.moe_aux_weight)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step_fn(params, opt, batch, step):
        if n_micro == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            micro = jax.tree.map(
                lambda a: a.reshape((n_micro, a.shape[0] // n_micro)
                                    + a.shape[1:]), batch)

            if scfg.grad_bucket_bytes:
                # bucketed accumulation: grads land in size-targeted flat
                # buffers; each bucket's add for microbatch k is
                # independent of microbatch k+1's backward, so the
                # scheduler can drain buckets under the next backward —
                # and the layout is the one a bucketed sync would ship.
                # Per element the fp32 adds are the pytree accumulation's,
                # so the update is bit-identical.
                plan = bucketing.bucket_plan(
                    jax.tree.map(
                        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                        params),
                    target_bytes=scfg.grad_bucket_bytes)

                def body(acc, mb):
                    (l, met), g = grad_fn(params, mb)
                    packed = bucketing.pack(g, plan)
                    acc = tuple(a + p for a, p in zip(acc, packed))
                    return acc, (l, met)

                zeros = tuple(jnp.zeros((m,), jnp.float32)
                              for m in plan.bucket_elements())
                bufs, (losses, mets) = lax.scan(body, zeros, micro)
                grads = bucketing.unpack(
                    [b / n_micro for b in bufs], plan)
            else:
                def body(g_acc, mb):
                    (l, met), g = grad_fn(params, mb)
                    g_acc = jax.tree.map(
                        lambda a, gg: a + gg.astype(jnp.float32), g_acc, g)
                    return g_acc, (l, met)

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                g_sum, (losses, mets) = lax.scan(body, zeros, micro)
                grads = jax.tree.map(lambda a: a / n_micro, g_sum)
            loss = losses.mean()
            metrics = {k: (v.sum() if k == "tokens" else v.mean())
                       for k, v in mets.items()}

        grads, grad_norm = clip_by_global_norm(grads, scfg.clip_norm)
        lr = warmup_cosine(step, peak_lr=scfg.peak_lr,
                           warmup_steps=scfg.warmup_steps,
                           total_steps=scfg.total_steps)
        new_params, new_opt = adamw_update(grads, opt, params, acfg, lr)
        metrics = dict(metrics, loss=loss, grad_norm=grad_norm, lr=lr)
        return new_params, new_opt, metrics

    psh = to_shardings(mesh, pspecs)
    osh = to_shardings(mesh, ospecs)
    bsh = to_shardings(mesh, bspecs)
    scalar = _scalar_sharding(mesh)
    # params and optimizer state are donated: the step replaces both, and
    # every caller rebinds them to its outputs
    fn = jax.jit(step_fn, in_shardings=(psh, osh, bsh, scalar),
                 out_shardings=(psh, osh, scalar), donate_argnums=(0, 1))
    return StepBundle(
        fn=fn,
        in_specs=(pspecs, ospecs, bspecs, P()),
        out_specs=(pspecs, ospecs, P()),
        aux={"params_shape": params_shape, "opt_shape": opt_shape},
    )


# ---------------------------------------------------------------------------
# prefill / serve
# ---------------------------------------------------------------------------


def build_prefill_step(cfg: ModelConfig, mesh, scfg: StepConfig,
                       batch: int, seq_len: int,
                       with_frontend: Optional[Tuple[int, int]] = None,
                       chunks: Optional[int] = None,
                       cache_len: Optional[int] = None) -> StepBundle:
    """``fn(params, tokens[, frontend_embeds]) -> (cache, logits)``:
    forward over the prompt that also materializes the decode cache.

    ``chunks`` > 1 builds the **chunked streamed prefill** instead: the
    prompt runs as that many ART chunks through
    ``pipeline.chunk_pipeline_carried`` so chunk *k*'s forward overlaps
    chunk *k−1*'s cache write (``models/prefill.prefill_chunked``) —
    bit-identical cache and logits to the bulk program (archs outside
    ``supports_chunked_prefill`` fall back to bulk).

    ``cache_len`` sizes the ring buffer independently of the prompt
    (default: the prompt length) — the server's per-slot admission prefill
    sizes it to the batched cache's ``max_seq``."""
    params_shape, _ = _state_shapes(cfg, scfg)
    pspecs = param_pspecs(cfg, mesh, params_shape)
    constrain = _constraint_fn(cfg, mesh, scfg)
    attn_runner = _attention_runner(cfg, mesh)
    dp = dp_axes(mesh)
    n_chunks = int(chunks or 1)
    cap = cache_len or seq_len

    def run(params, tokens, fe=None):
        if n_chunks > 1:
            return prefill_chunked(cfg, params, tokens, fe,
                                   cache_len=cap, n_chunks=n_chunks)
        return prefill(cfg, params, tokens, fe, cache_len=cap)

    arg_shapes = [jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)]
    arg_specs = [P(fit_axis(mesh, dp, batch), None)]
    if with_frontend is not None:
        n_tok, n_dim = with_frontend
        arg_shapes.append(
            jax.ShapeDtypeStruct((batch, n_tok, n_dim), jnp.float32))
        arg_specs.append(P(arg_specs[0][0], None, None))

    # the program is ``jit_fwd`` in a profiler trace: keep the name, trace
    # readers match it
    if with_frontend is None:
        def raw(params, tokens):
            return run(params, tokens)

        def fwd(params, tokens):
            with activation_sharding(constrain, attention=attn_runner):
                return run(params, tokens)
    else:
        def raw(params, tokens, fe):
            return run(params, tokens, fe)

        def fwd(params, tokens, fe):
            with activation_sharding(constrain, attention=attn_runner):
                return run(params, tokens, fe)

    cache_shape, logits_shape = jax.eval_shape(raw, params_shape, *arg_shapes)
    cspecs = cache_pspecs(cfg, mesh, cache_shape)
    lspec = P(arg_specs[0][0], None)

    fn = jax.jit(
        fwd,
        in_shardings=(to_shardings(mesh, pspecs),
                      *[NamedSharding(mesh, s) for s in arg_specs]),
        out_shardings=(to_shardings(mesh, cspecs), NamedSharding(mesh, lspec)))
    return StepBundle(
        fn=fn,
        in_specs=(pspecs, *arg_specs),
        out_specs=(cspecs, lspec),
        aux={"params_shape": params_shape, "cache_shape": cache_shape,
             "logits_shape": logits_shape},
    )


def _moe_decode_runner(cfg: ModelConfig, mesh, policy: TransportPolicy,
                       batch: int) -> Optional[Callable]:
    """The latency-mode EP decode runner, or None (dense-combine decode).

    ``policy.moe`` non-``xla`` with a usable ``expert`` axis batches the
    step's B decode tokens across the expert shards through
    ``Conduit("expert").all_to_all`` (``models/moe_ep.py`` with
    ``decode=True``).  Batches the mesh cannot split keep dense-combine —
    the weight-bound small-batch fallback."""
    if policy.moe == "xla" or not serving_features(cfg)["ep_decode"]:
        return None
    if batch % mesh.size:
        warnings.warn(
            f"TransportPolicy.moe={policy.moe!r} requested but the serve "
            f"batch ({batch}) does not divide the mesh ({mesh.size}); "
            f"decode keeps the dense-combine fallback", stacklevel=3)
        return None
    return moe_ep.build_moe_ep_runner(
        cfg, mesh, transport=policy.moe, chunk_bytes=policy.chunk_bytes,
        decode=True)


def build_serve_step(cfg: ModelConfig, mesh, scfg: StepConfig,
                     batch: int, max_seq: int, *,
                     sample: bool = False,
                     block_size: int | None = None,
                     n_blocks: int | None = None) -> StepBundle:
    """``fn(params, cache, tokens) -> (cache, logits | token_ids)``: one
    batched decode step against the ring-buffer cache (continuous-batching
    inner loop; every cache row advances at its own per-slot position).

    The cache is **donated** — in/out shardings match leaf-for-leaf, so
    the output cache takes the input's memory.  On the contiguous GQA
    ring the step writes each row's new K/V vector per layer in place
    and copies no slab of the ring (``models/decode.decode_step``), as
    on the MLA latent rings; the paged, encdec and hybrid caches still
    pass through the layer scan whole.

    ``sample=True`` returns greedy-sampled ``(B,)`` int32 token ids instead
    of the (B, V) logits: argmax runs on device and the server fetches one
    stacked id vector per step instead of syncing per-slot logits.

    ``block_size`` ≠ None switches the cache template to the paged block
    pool (``models/decode.init_paged_cache``): decode gathers each row's
    ring through its ``block_ids`` table and scatters the new row back into
    the pool — bit-identical to the contiguous path when every table fully
    backs the ring.  ``n_blocks`` defaults to parking blocks plus a full
    private table per row.

    ``TransportPolicy.moe`` ≠ ``xla`` (with an ``expert`` mesh axis and a
    mesh-divisible batch) swaps the dense-combine MoE decode for the
    expert-parallel conduit dispatch — see :func:`_moe_decode_runner`.
    """
    params_shape, _ = _state_shapes(cfg, scfg)
    pspecs = param_pspecs(cfg, mesh, params_shape)
    if block_size is not None:
        npb = paged_slot_blocks(cfg, max_seq, block_size)
        if n_blocks is None:
            n_blocks = batch * (1 + npb)
        cache_shape = jax.eval_shape(
            lambda: init_paged_cache(cfg, batch, max_seq, block_size,
                                     n_blocks))
    else:
        cache_shape = jax.eval_shape(lambda: init_cache(cfg, batch, max_seq))
    cspecs = cache_pspecs(cfg, mesh, cache_shape)
    dp = dp_axes(mesh)
    b_entry = fit_axis(mesh, dp, batch)
    tok_spec = P(b_entry)
    out_spec = P(b_entry) if sample else P(b_entry, None)
    moe_runner = _moe_decode_runner(cfg, mesh, scfg.resolved_transport(),
                                    batch)

    # the program is ``jit_fn`` in a profiler trace, and no other program
    # the server runs has that name: keep it, trace readers match it
    def fn_(params, cache, tokens):
        cache, logits = decode_step(cfg, params, cache, tokens,
                                    moe_runner=moe_runner)
        if sample:
            return cache, jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return cache, logits

    fn = jax.jit(
        fn_,
        in_shardings=(to_shardings(mesh, pspecs),
                      to_shardings(mesh, cspecs),
                      NamedSharding(mesh, tok_spec)),
        out_shardings=(to_shardings(mesh, cspecs),
                       NamedSharding(mesh, out_spec)),
        donate_argnums=(1,))
    return StepBundle(
        fn=fn,
        in_specs=(pspecs, cspecs, tok_spec),
        out_specs=(cspecs, out_spec),
        aux={"params_shape": params_shape, "cache_shape": cache_shape},
    )


def build_prefill_chunk_step(cfg: ModelConfig, mesh, scfg: StepConfig,
                             batch: int, prompt_len: int,
                             lo: int, chunk_len: int,
                             with_frontend: Optional[Tuple[int, int]] = None,
                             ) -> StepBundle:
    """``fn(params, scratch, tokens[, frontend]) -> (scratch, logits)``: one
    incremental prefill chunk at static offset ``lo`` (the server's
    admission step), for whichever carry kind the arch declares
    (``configs.base.chunk_carry_spec``).

    The scratch is **donated** (same spec in and out), so each chunk
    updates the carry buffers in place; the final chunk's logits seed the
    request's first decode token.  ``with_frontend=(n_rows, dim)`` adds a
    frontend-embedding argument — the chunk's fe-row slice for vlm, the
    full frame tensor on the encdec chunk 0.  Requires
    ``models/prefill.chunk_support(cfg)``.
    """
    ok, why = chunk_support(cfg)
    assert ok, f"{cfg.name}: {why}"
    params_shape, _ = _state_shapes(cfg, scfg)
    pspecs = param_pspecs(cfg, mesh, params_shape)
    constrain = _constraint_fn(cfg, mesh, scfg)
    scratch_shape = jax.eval_shape(
        lambda: init_prefill_scratch(cfg, batch, prompt_len))
    sspecs = cache_pspecs(cfg, mesh, scratch_shape)
    dp = dp_axes(mesh)
    b_entry = fit_axis(mesh, dp, batch)
    tok_spec = P(b_entry, None)
    logit_spec = P(b_entry, None)

    if with_frontend is not None:
        fe_spec = P(b_entry, None, None)

        def prefill_chunk_step(params, scratch, tokens, frontend):
            with activation_sharding(constrain):
                return prefill_chunk(cfg, params, scratch, tokens, lo,
                                     frontend_embeds=frontend)

        fn = jax.jit(
            prefill_chunk_step,
            in_shardings=(to_shardings(mesh, pspecs),
                          to_shardings(mesh, sspecs),
                          NamedSharding(mesh, tok_spec),
                          NamedSharding(mesh, fe_spec)),
            out_shardings=(to_shardings(mesh, sspecs),
                           NamedSharding(mesh, logit_spec)),
            donate_argnums=(1,))
        return StepBundle(
            fn=fn,
            in_specs=(pspecs, sspecs, tok_spec, fe_spec),
            out_specs=(sspecs, logit_spec),
            aux={"params_shape": params_shape,
                 "scratch_shape": scratch_shape,
                 "lo": lo, "chunk_len": chunk_len,
                 "with_frontend": with_frontend},
        )

    def prefill_chunk_step(params, scratch, tokens):
        with activation_sharding(constrain):
            return prefill_chunk(cfg, params, scratch, tokens, lo)

    fn = jax.jit(
        prefill_chunk_step,
        in_shardings=(to_shardings(mesh, pspecs),
                      to_shardings(mesh, sspecs),
                      NamedSharding(mesh, tok_spec)),
        out_shardings=(to_shardings(mesh, sspecs),
                       NamedSharding(mesh, logit_spec)),
        donate_argnums=(1,))
    return StepBundle(
        fn=fn,
        in_specs=(pspecs, sspecs, tok_spec),
        out_specs=(sspecs, logit_spec),
        aux={"params_shape": params_shape, "scratch_shape": scratch_shape,
             "lo": lo, "chunk_len": chunk_len},
    )


def build_slot_write_step(cfg: ModelConfig, mesh, batch: int,
                          max_seq: int) -> StepBundle:
    """``fn(cache, slot_cache, i) -> cache``: write a single-request cache
    (batch 1) into row ``i`` of every leaf of the batched decode cache —
    the per-slot admission PUT of the continuous-batching server.  The
    batched cache is **donated**; only row ``i`` moves.  The program is
    named ``jit_slot_write``."""
    full_shape = jax.eval_shape(lambda: init_cache(cfg, batch, max_seq))
    one_shape = jax.eval_shape(lambda: init_cache(cfg, 1, max_seq))
    # the batch axis of each leaf, found structurally (it differs per
    # family: (L, B, ...) stacks vs (B, ...) bookkeeping)
    two_shape = jax.eval_shape(lambda: init_cache(cfg, 2, max_seq))
    baxes = {
        k: next(i for i, (a, b) in enumerate(
            zip(two_shape[k].shape, one_shape[k].shape)) if a != b)
        for k in one_shape
    }
    cspecs = cache_pspecs(cfg, mesh, full_shape)
    sspecs = cache_pspecs(cfg, mesh, one_shape)

    def slot_write(cache, slot, i):
        return {
            k: lax.dynamic_update_slice_in_dim(
                cache[k], slot[k].astype(cache[k].dtype), i, axis=baxes[k])
            for k in cache
        }

    fn = jax.jit(
        slot_write,
        in_shardings=(to_shardings(mesh, cspecs),
                      to_shardings(mesh, sspecs), _scalar_sharding(mesh)),
        out_shardings=to_shardings(mesh, cspecs),
        donate_argnums=(0,))
    return StepBundle(
        fn=fn,
        in_specs=(cspecs, sspecs, P()),
        out_specs=cspecs,
        aux={"cache_shape": full_shape, "batch_axes": baxes},
    )


def build_block_write_step(cfg: ModelConfig, mesh, batch: int,
                           max_seq: int, block_size: int, n_blocks: int,
                           n_write: int) -> StepBundle:
    """``fn(cache, bk, bv, dst, table_row, slot_pos_row, pos, i) -> cache``:
    push ``n_write`` finished prefill blocks into the paged pool and install
    row ``i``'s block table — the block-granular admission PUT.

    ``bk``/``bv`` are ``(L, n_write, Hkv, blk, hd)`` block stacks (from
    ``models/prefill.scratch_to_blocks``), ``dst`` the ``(n_write,)`` global
    pool ids they land in, ``table_row`` the full ``(S_buf/blk,)`` table for
    the slot (private ids plus any ref-counted shared-prefix ids, which are
    *not* rewritten — copy-on-write sharing).  The pool cache is **donated**;
    only the written blocks and row ``i``'s bookkeeping move.  One bundle
    per ``n_write`` — the server caches them per distinct prefix-hit depth.
    """
    full_shape = jax.eval_shape(
        lambda: init_paged_cache(cfg, batch, max_seq, block_size, n_blocks))
    cspecs = cache_pspecs(cfg, mesh, full_shape)

    def block_write(cache, bk, bv, dst, table_row, slot_pos_row, pos, i):
        out = dict(cache)
        out["kp"] = cache["kp"].at[:, dst].set(bk.astype(cache["kp"].dtype))
        out["vp"] = cache["vp"].at[:, dst].set(bv.astype(cache["vp"].dtype))
        out["block_ids"] = lax.dynamic_update_slice_in_dim(
            cache["block_ids"], table_row[None], i, axis=0)
        out["slot_pos"] = lax.dynamic_update_slice_in_dim(
            cache["slot_pos"], slot_pos_row[None], i, axis=0)
        out["pos"] = lax.dynamic_update_slice_in_dim(
            cache["pos"], pos[None], i, axis=0)
        return out

    # payload inputs keep whatever sharding prefill left them with (the
    # scatter re-lays them out); only the donated pool is pinned.
    fn = jax.jit(
        block_write,
        in_shardings=(to_shardings(mesh, cspecs),) + (None,) * 7,
        out_shardings=to_shardings(mesh, cspecs),
        donate_argnums=(0,))
    return StepBundle(
        fn=fn,
        in_specs=(cspecs, P(), P(), P(), P(), P(), P(), P()),
        out_specs=cspecs,
        aux={"cache_shape": full_shape, "n_write": n_write,
             "block_size": block_size},
    )


__all__ = [
    "StepConfig", "StepBundle", "TransportPolicy", "refit_step_config",
    "build_init",
    "build_train_step", "build_prefill_step", "build_serve_step",
    "build_prefill_chunk_step", "build_slot_write_step",
    "build_block_write_step", "MeshAxes",
]
