"""Plain reference of the served model, in float32, and the comparison
of served tokens against it.

The forward is the configuration's family's (``logits`` in
``chipbench/families/<model_type>.py``), written from the published
architecture with the building blocks here.  It imports nothing of the
program and takes nothing the program made: the weights come from
``weights.make`` and the inputs from the traffic generators.  Every
matrix product runs at ``highest`` precision.

``precision="fp8"`` is the control: the same model with every matrix
product's operands rounded to float8 (e4m3) with one scale per tensor,
the step below the bf16 that the configurations state.

Memory: the forward runs one sequence at a time and attention one block
of ``Q_BLOCK`` queries at a time, so a long sequence fits beside nothing
else.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import families

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


def logits(hf: Dict, params: Dict, tokens, precision: str = "f32"):
    """(S,) int tokens -> (S, V) float32 logits: the forward of the
    configuration's family (``chipbench/families/<model_type>.py``)."""
    return families.load(hf).logits(hf, params, tokens, precision)


def to_f32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


# -- serving: the gap of a served token below the reference's best ---------


@functools.lru_cache(maxsize=None)
def _gap_fn(hf_key: str, precision: str):
    hf = families.thaw(hf_key)

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
    gap, _ = _gap_fn(families.freeze(hf), precision)(params_f32, padded, query)
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
    key = families.freeze(hf)
    _, top = _gap_fn(key, precision)(params_f32, padded, padded)
    gap, _ = _gap_fn(key, "f32")(params_f32, padded, top)
    lo = prompt.size - 1
    return np.asarray(gap)[lo:n - 1]


__all__ = ["logits", "to_f32", "served_gaps", "control_gaps"]
