"""Random weights from the seed, made on the device in one jitted call.

The tree and each leaf's spread are the configuration's family's
(``chipbench/families/<model_type>.py``: the layout the program's model
of that family takes).  It is built from the configuration file alone, so
the plain reference can make the same weights without importing the
program; the harness checks the tree against the program's own shapes
before it runs.  Leaf ``i`` of the sorted paths draws from the seed's key
folded with ``i``.

Scales keep every layer's contribution of the order of the residual, so a
fault in any layer shows in the logits; norm scales are 1 + N(0, 0.01).
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import families


def shapes(hf: Dict) -> Dict:
    """``{path: shape}`` of every leaf, paths joined by ``/``: the
    family's tree (``chipbench/families/<model_type>.py``)."""
    return families.load(hf).shapes(hf)


def root_key(seed: int):
    """A PRNG key from a seed of any size (64 bits are kept)."""
    seed = int(seed) % (1 << 64)
    k = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(seed >> 32))


def nest(flat: Dict) -> Dict:
    tree: Dict = {}
    for path, leaf in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


def flatten(tree: Dict, prefix: str = "") -> Dict:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, p))
        else:
            out[p] = v
    return out


@functools.lru_cache(maxsize=None)
def _maker(hf_key: str, dtype: str):
    hf = families.thaw(hf_key)
    fam = families.load(hf)
    shp = fam.shapes(hf)

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, s) in enumerate(sorted(shp.items())):
            k = jax.random.fold_in(key, i)
            if path.endswith("/scale"):
                x = 1.0 + 0.01 * jax.random.normal(k, s, jnp.float32)
            else:
                x = fam.std(hf, path, s) * jax.random.normal(
                    k, s, jnp.float32)
            flat[path] = x.astype(dtype)
        return nest(flat)

    return make


def make(hf: Dict, seed: int, dtype: str = "bfloat16") -> Dict:
    """The weights for ``seed``, in ``dtype``.  The reference asks for the
    same bf16 values and widens them to float32 itself."""
    return _maker(families.freeze(hf), dtype)(root_key(seed))


__all__ = ["shapes", "make", "nest", "flatten", "root_key"]
