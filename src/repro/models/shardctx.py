"""Activation-sharding context: the step builder injects sharding
constraints into the (mesh-agnostic) model code.

The step builders in ``repro.dist.steps`` install a tag→constraint function
for the duration of a trace (``build_train_step`` / ``build_prefill_step``
via :func:`activation_sharding`); model code calls
``constrain(x, "residual")`` at block boundaries.
Outside any context this is the identity, so model code runs unchanged in
unit tests / single-device smoke tests.

Tags used by the model zoo:
  residual   — the (B, S, D) stream at layer boundaries (SP shards S on tp)
  logit_hidden — final hidden entering the LM head
"""

from __future__ import annotations

import contextlib
from typing import Callable, Optional

_ACTIVE: Optional[Callable] = None
_TP_BLOCK: Optional[Callable] = None
_MOE_FFN: Optional[Callable] = None
_ATTN: Optional[Callable] = None


@contextlib.contextmanager
def activation_sharding(fn: Callable, tp_block: Optional[Callable] = None,
                        moe_ffn: Optional[Callable] = None,
                        attention: Optional[Callable] = None):
    """``fn(x, tag)`` applies sharding constraints.

    ``tp_block`` (optional) is the ART-TP dense-block runner installed by
    ``repro.dist.steps.build_train_step`` when ``TransportPolicy.tp`` names
    a ring family: ``tp_block(cfg, layer_params, x, positions) -> x``
    executes the block with hand-scheduled ring collectives
    (models/artblock.py).

    ``moe_ffn`` (optional) is the expert-parallel MoE runner installed when
    ``TransportPolicy.moe`` names a conduit transport and the mesh has an
    ``expert`` axis: ``moe_ffn(cfg, moe_params, x) -> y`` replaces
    ``layers.moe`` with the bucketed all_to_all dispatch of
    ``models/moe_ep.py``.

    ``attention`` (optional) is the Pallas attention runner installed when
    the mesh has more than one device: ``attention(q, k, v, causal=,
    window=, scale=) -> out`` runs the flash kernel inside a ``shard_map``
    over the axes that shard batch and heads (GSPMD cannot partition a
    Mosaic kernel)."""
    global _ACTIVE, _TP_BLOCK, _MOE_FFN, _ATTN
    old = _ACTIVE, _TP_BLOCK, _MOE_FFN, _ATTN
    _ACTIVE, _TP_BLOCK, _MOE_FFN, _ATTN = fn, tp_block, moe_ffn, attention
    try:
        yield
    finally:
        _ACTIVE, _TP_BLOCK, _MOE_FFN, _ATTN = old


def constrain(x, tag: str):
    if _ACTIVE is None:
        return x
    return _ACTIVE(x, tag)


def tp_block_runner() -> Optional[Callable]:
    return _TP_BLOCK


def moe_ffn_runner() -> Optional[Callable]:
    """The installed expert-parallel MoE runner, or None (dense GSPMD)."""
    return _MOE_FFN


def attention_runner() -> Optional[Callable]:
    """The installed sharded Pallas attention runner, or None (call the
    kernel directly: one device, or outside a step builder)."""
    return _ATTN
