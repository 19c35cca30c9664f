"""Sharding rules: model pytrees → PartitionSpec trees (GSPMD side).

One table of positional rules maps every parameter / cache / batch leaf to a
``PartitionSpec`` over the logical axes in :class:`MeshAxes`:

  * column-parallel projections (``wq``/``w_up``/...) put their output dim on
    ``model`` and their input dim on ``data`` (Megatron TP × ZeRO/FSDP);
  * row-parallel projections (``wo``/``w_down``/...) are the transpose;
  * the embedding is vocab-parallel (``model`` on the vocab dim);
  * norms/scalars replicate.

Rules are *right-aligned* against the leaf shape, so the same table covers a
bare layer and the ``lax.scan``-stacked layer pytree (the leading layer axis
— and the MoE expert axis — pad with ``None``).

Every assignment is guarded by a divisibility check against the mesh: an
axis whose extent does not divide the dim is dropped (replicated) rather
than emitted, so irregular vocab/head counts degrade gracefully instead of
failing to place (the fallback asserted by ``tests/test_dist.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

AxisEntry = Union[str, Tuple[str, ...], None]


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Logical roles of mesh axes.

    ``data``: DP/FSDP axes — a tuple (e.g. ``("pod", "data")``) spans the
    cross-pod DCN hop; ``model``: tensor/sequence parallelism (ICI);
    ``expert``: expert parallelism — MoE expert weights shard their leading
    E dim over it, and for everything *else* it behaves as one more data
    axis (tokens shard over it, dense params replicate along it), which is
    what lets ``models/moe_ep.py`` route distinct tokens per expert shard.
    """

    data: AxisEntry = "data"
    model: AxisEntry = "model"
    expert: AxisEntry = "expert"

    def names(self, entry: AxisEntry) -> Tuple[str, ...]:
        """An entry as a flat tuple of mesh-axis names (None → empty)."""
        if entry is None:
            return ()
        return (entry,) if isinstance(entry, str) else tuple(entry)


def dp_axes(mesh) -> AxisEntry:
    """The full data-parallel axis set of ``mesh``.

    Includes ``pod`` (DCN) and ``expert`` when present: the expert axis
    carries distinct tokens like any data axis — only MoE expert weights
    treat it specially (see :func:`param_pspecs`)."""
    names = tuple(a for a in ("pod", "data", "expert")
                  if a in mesh.axis_names)
    if not names:
        return None
    return names[0] if len(names) == 1 else names


def fit_axis(mesh, entry: AxisEntry, dim: int) -> AxisEntry:
    """``entry`` if every named axis exists and their product divides
    ``dim``; otherwise ``None`` (replicate — the divisibility fallback).
    The single owner of the drop-don't-fail placement rule; every spec
    builder (here and in ``dist/steps.py``) goes through it."""
    if entry is None:
        return None
    names = (entry,) if isinstance(entry, str) else tuple(entry)
    size = 1
    for name in names:
        if name not in mesh.axis_names:
            return None
        size *= mesh.shape[name]
    return entry if (size > 0 and dim % size == 0) else None


# -- parameter rules ---------------------------------------------------------

# (in_dim, out_dim) projections: output column-sharded on model, input on
# data.  Covers GQA/MLA attention, dense/MoE MLPs and the Mamba projections.
_COL_PARALLEL = frozenset({
    "wq", "wk", "wv", "w_up", "w_gate", "in_proj",
    "w_q", "w_dq", "w_dkv", "w_uq", "w_uk", "w_uv",
})
# (in_dim, out_dim) with the *input* dim model-sharded (row-parallel).
_ROW_PARALLEL = frozenset({"wo", "w_down", "out_proj"})
# Small per-layer vectors: replicate.
_REPLICATED = frozenset({
    "scale", "bias", "dt_bias", "a_log", "d_skip", "conv_b", "step",
})
# MoE routed-expert tensors (leading E dim under params["moe"]): the E dim
# shards over the ``expert`` axis when the mesh has one.
_EXPERT_PARALLEL = frozenset({"w_up", "w_gate", "w_down"})


def _param_rule(name: str, shape: Tuple[int, ...], mesh,
                axes: MeshAxes, parent: str = "") -> P:
    d, m = axes.data, axes.model
    if name in _REPLICATED or len(shape) == 0:
        return P()
    if name == "embed":
        base: Tuple[AxisEntry, ...] = (m, d)      # vocab-parallel
    elif name == "lm_head":
        base = (d, m)
    elif name in _COL_PARALLEL:
        base = (d, m)
    elif name in _ROW_PARALLEL:
        base = (m, d)
    elif name == "router":
        base = (d, None)
    elif name == "conv_w":
        base = (None, m)
    elif name in ("dec_pos", "frontend_proj"):
        base = (None, d)
    else:
        return P()
    # routed-expert tensors carry a leading E dim ahead of the (in, out)
    # pair; ``parent == "moe"`` distinguishes them from the same-named
    # dense projections (incl. the shared expert under "shared")
    if parent == "moe" and name in _EXPERT_PARALLEL and len(shape) >= 3:
        base = (axes.expert,) + base
    k = min(len(base), len(shape))
    base = base[len(base) - k:]
    tail = shape[len(shape) - k:]
    entries = [None] * (len(shape) - k)
    entries += [fit_axis(mesh, e, dim) for e, dim in zip(base, tail)]
    return P(*entries)


def _leaf_name(path, idx: int = -1) -> str:
    if len(path) < -idx:
        return ""
    last = path[idx]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def param_pspecs(cfg: ModelConfig, mesh, params,
                 axes: Optional[MeshAxes] = None):
    """PartitionSpec tree for a parameter pytree (arrays or shape structs).

    Parameters stay *within-pod*: the default axes never shard over ``pod``
    — only the gradient all-reduce crosses the DCN (DESIGN §6).  MoE
    routed-expert weights additionally shard their leading E dim over the
    ``expert`` axis when the mesh has one (expert parallelism)."""
    del cfg  # rules are shape/name driven; cfg kept for API stability
    axes = axes or MeshAxes()
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [_param_rule(_leaf_name(path), tuple(leaf.shape), mesh, axes,
                         parent=_leaf_name(path, -2))
             for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def opt_pspecs(cfg: ModelConfig, mesh, opt, pspecs,
               axes: Optional[MeshAxes] = None) -> Dict[str, Any]:
    """Optimizer-state specs mirror the parameter specs leaf-for-leaf
    (ZeRO: moments and the fp32 master shard exactly like their param)."""
    del cfg, mesh, axes
    return {key: (P() if key == "step" else pspecs) for key in opt}


# -- cache / batch rules -----------------------------------------------------

_KV_LIKE = frozenset({
    "k", "v", "attn_k", "attn_v", "cross_k", "cross_v", "ckv", "krope",
})


def cache_pspecs(cfg: ModelConfig, mesh, cache,
                 axes: Optional[MeshAxes] = None) -> Dict[str, P]:
    """Decode-cache specs: batch on ``data``; the KV ring-buffer sequence dim
    on ``model`` (sequence-sharded cache — robust to n_kv < tp); SSM heads on
    ``model``; position bookkeeping replicated."""
    del cfg
    axes = axes or MeshAxes()
    d, m = axes.data, axes.model
    specs: Dict[str, P] = {}
    for key, leaf in cache.items():
        shape = tuple(leaf.shape)
        if key in ("pos", "slot_pos", "block_ids") or len(shape) < 2:
            specs[key] = P()                    # bookkeeping: replicate
            continue
        entries: list = [None] * len(shape)
        entries[1] = fit_axis(mesh, d, shape[1])          # (stack, batch, ...)
        if key in _KV_LIKE and len(shape) >= 3:
            entries[-2] = fit_axis(mesh, m, shape[-2])    # sequence/buffer dim
        elif key == "ssm_state" and len(shape) >= 3:
            entries[2] = fit_axis(mesh, m, shape[2])      # SSM heads
        elif key == "conv_state":
            entries[-1] = fit_axis(mesh, m, shape[-1])    # conv channels
        specs[key] = P(*entries)
    return specs


def batch_pspecs(mesh, batch, axes: Optional[MeshAxes] = None):
    """Batch specs: leading (example) dim over the *full* DP axis set —
    including ``pod`` when present; everything else replicated."""
    dp = axes.data if axes is not None else dp_axes(mesh)

    def rule(leaf) -> P:
        shape = tuple(leaf.shape)
        if not shape:
            return P()
        return P(fit_axis(mesh, dp, shape[0]), *([None] * (len(shape) - 1)))

    return jax.tree.map(rule, batch)


def to_shardings(mesh, specs):
    """PartitionSpec tree → NamedSharding tree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
