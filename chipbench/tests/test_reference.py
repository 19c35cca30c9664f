"""The plain reference agrees with the program at a tiny size, and its
lower-precision control is told apart by the check."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program, reference, weights
from chipbench.tests import tiny

SEED = 2**31 + 99


def _hf(window=None, tie=True):
    hf = {"arch": "smollm-360m", "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
          "hidden_act": "silu", "tie_word_embeddings": tie,
          "torch_dtype": "float32", "max_position_embeddings": 4096}
    return dict(hf, **tiny.TINY_CONFIG, sliding_window=window)


@pytest.mark.parametrize("window,tie", [(None, True), (8, False)])
def test_reference_logits_equal_the_programs(window, tie):
    from repro.models.model import forward

    hf = _hf(window, tie)
    cfg = dataclasses.replace(program.model_config(hf), attn_impl="jnp",
                              attn_q_chunk=8, attn_kv_chunk=8)
    program.check_param_shapes(cfg, hf)
    params = weights.make(hf, SEED, "float32")
    toks = np.random.default_rng(0).integers(0, 256, size=40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(cfg, params, jnp.asarray(toks[None]))
        want = reference.logits(hf, params, jnp.asarray(toks))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_control_gaps_exceed_the_programs():
    """fp8 in the program's place reads wider gaps than bf16 does."""
    hf = _hf()
    params = reference.to_f32(weights.make(hf, SEED))
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 256, size=24).astype(np.int32)
    served = rng.integers(0, 256, size=24).astype(np.int32)
    ctl = reference.control_gaps(hf, params, prompt, served, 64, "fp8")
    ref = reference.served_gaps(hf, params, prompt, served, 64)
    assert ctl.shape == ref.shape == (24,)
    # the served tokens here are random: the reference's own best reads 0
    top = reference.control_gaps(hf, params, prompt, served, 64, "f32")
    assert np.all(top <= 1e-6)
    assert ctl.max() > tiny.SERVE["check"]["served_logit_gap"]
