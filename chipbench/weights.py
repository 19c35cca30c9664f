"""Random weights from the seed, made on the device in one jitted call.

The tree has the layout the program's dense GQA model takes (layers
stacked on a leading axis).  It is built from the configuration file
alone, so the plain reference can make the same weights without importing
the program; the harness checks the tree against the program's own shapes
before it runs.

Scales keep every layer's contribution of the order of the residual, so a
fault in any layer shows in the logits: matrices N(0, 1/fan_in), the
embedding (and an untied LM head) N(0, 4/hidden_size), which gives logits
of about unit-2 spread, and norm scales 1 + N(0, 0.01).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def shapes(hf: Dict) -> Dict:
    """``{path: shape}`` of every leaf, paths joined by ``/``."""
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


def _std(path: str, shape: Tuple[int, ...], d: int) -> float:
    if path in ("embed", "lm_head"):
        return 2.0 / np.sqrt(d)
    return 1.0 / np.sqrt(shape[-2])


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
def _maker(hf_items: tuple, dtype: str):
    hf = dict(hf_items)
    shp = shapes(hf)
    d = hf["hidden_size"]

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, s) in enumerate(sorted(shp.items())):
            k = jax.random.fold_in(key, i)
            if path.endswith("/scale"):
                x = 1.0 + 0.01 * jax.random.normal(k, s, jnp.float32)
            else:
                x = _std(path, s, d) * jax.random.normal(k, s, jnp.float32)
            flat[path] = x.astype(dtype)
        return nest(flat)

    return make


def make(hf: Dict, seed: int, dtype: str = "bfloat16") -> Dict:
    """The weights for ``seed``, in ``dtype``.  The reference asks for the
    same bf16 values and widens them to float32 itself."""
    items = tuple(sorted((k, v) for k, v in hf.items()
                         if isinstance(v, (int, float, bool, str))
                         or v is None))
    return _maker(items, dtype)(root_key(seed))


__all__ = ["shapes", "make", "nest", "flatten", "root_key"]
