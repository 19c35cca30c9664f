"""Varying-manual-axes helpers for shard_map code (vma tracking).

Inside ``shard_map``, constants are *unvarying* over the mesh axes while
anything derived from permuted/indexed data is *varying*.  ``lax.scan`` /
``lax.fori_loop`` carries and ``lax.switch`` branches must agree on vma, so
loop initializers and handler outputs built from ``jnp.zeros`` need an
explicit promotion.  ``lax.pcast(..., to='varying')`` errors when the value
is already varying; these helpers make the promotion idempotent.
"""

from __future__ import annotations

import jax
from jax import lax


def vary(x, axis: str | tuple[str, ...]):
    """Promote ``x`` to varying over ``axis`` (no-op if already varying)."""
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    vma = getattr(jax.typeof(x), "vma", frozenset())
    missing = tuple(a for a in axes if a not in vma)
    if not missing:
        return x
    return lax.pcast(x, missing, to="varying")


def vary_tree(tree, axis: str | tuple[str, ...] | None):
    """:func:`vary` over every leaf of ``tree`` (None axis: no-op)."""
    if axis is None:
        return tree
    return jax.tree.map(lambda x: vary(x, axis), tree)
