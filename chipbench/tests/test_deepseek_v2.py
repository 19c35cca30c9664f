"""The DeepSeek-V2 family on the CPU, at a tiny size: the program against
the family's plain reference, a whole tiny run of the cell `correct` and
a control with one expert's output dropped not, the experts' shares
against the uncut layer, and the counts."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import families, flops, program, reference, run, weights
from chipbench.tests import tiny

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "deepseek-v2-lite.chat"
SEED = 7

#: the tiny stand-in: one dense and two MoE layers, 4 of 8 experts held,
#: top-2, every width small; the rest as the configuration file has it
TINY = {"hidden_size": 64, "intermediate_size": 160,
        "moe_intermediate_size": 48, "num_hidden_layers": 3,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "vocab_size": 256, "n_routed_experts": 4,
        "num_experts_per_tok": 2,
        "published": {"num_hidden_layers": 27, "n_routed_experts": 8}}

#: from CPU readings at this size over six seeds (2^31+4243, 7, 11,
#: 2^33+5, 99, 123456789; 12 requests each): the bf16 program 0.030-0.325
#: (top-k near-ties flip a held expert in or out), the fp8 control
#: 1.66-2.93, expert 0's output dropped 1.49-3.53
CHECK = {"requests": 16, "served_logit_gap": 0.8}


def _hf(**extra):
    with open(os.path.join(HERE, "configs", "deepseek-v2-lite.json")) as f:
        return {**json.load(f), **TINY, **extra}


def _overrides():
    return {"config": dict(TINY),
            "workload": dict(tiny.SERVE, check=dict(CHECK))}


def test_program_matches_reference_prefill_then_decode():
    """Bulk prefill, then decode through the carried latent ring, against
    the family's float32 reference on the same seeded weights."""
    from repro.models.decode import decode_step
    from repro.models.prefill import prefill

    hf = _hf(torch_dtype="float32")
    cfg = dataclasses.replace(program.model_config(hf), attn_impl="jnp")
    program.check_param_shapes(cfg, hf)
    params = weights.make(hf, SEED, "float32")
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 40), 0,
                              hf["vocab_size"])
    with jax.default_matmul_precision("highest"):
        ref = np.stack([np.asarray(reference.logits(hf, params, t))
                        for t in toks])
    cache, lg = prefill(cfg, params, toks[:, :24], cache_len=48)
    got = [np.asarray(lg)]
    for t in range(24, 39):
        cache, lg = decode_step(cfg, params, cache, toks[:, t])
        got.append(np.asarray(lg))
    np.testing.assert_allclose(np.stack(got, 1), ref[:, 23:39],
                               rtol=1e-4, atol=1e-4)


def test_family_pins_yarn():
    fam = families.load(_hf())
    hf = _hf(qk_rope_head_dim=64, qk_nope_head_dim=128)
    assert fam.yarn_range(hf) == (10, 23)
    assert fam.softmax_scale(hf) == pytest.approx(0.114721, abs=1e-6)


def test_tiny_run_correct():
    out = run.execute(CELL, SEED, 1.0, False, require_chip=False,
                      overrides=_overrides())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_dropping_one_expert_is_not_correct(monkeypatch):
    """The control: the program with held expert 0's output left out."""
    from repro.models import layers as L

    topk = L.moe_topk

    def drop_first(cfg, router, xc):
        w, idx = topk(cfg, router, xc)
        return jnp.where(idx == cfg.expert_offset, 0.0, w), idx

    monkeypatch.setattr(L, "moe_topk", drop_first)
    out = run.execute(CELL, SEED, 1.0, False, require_chip=False,
                      overrides=_overrides())
    assert not out["correct"], out["checks"]


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of 2 of 8 experts each: the program's routed parts of
    the four shares, with the shared experts counted once, are the uncut
    reference MoE layer."""
    from repro.models import layers as L

    fam = families.load(_hf())
    uncut = _hf(torch_dtype="float32", n_routed_experts=8)
    params = weights.make(uncut, SEED, "float32")
    mp = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (11, uncut["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        want = fam._moe(uncut, "f32", x, mp)
        shared = L.mlp(program.model_config(uncut), mp["shared"], x[None])[0]
        total = shared
        for chip in range(4):
            hf = _hf(torch_dtype="float32", n_routed_experts=2,
                     expert_parallel={"chips": 4, "first_expert": 2 * chip})
            cfg = program.model_config(hf)
            part = {**mp, **{k: mp[k][2 * chip:2 * chip + 2]
                             for k in ("w_up", "w_gate", "w_down")}}
            total = total + L.moe(cfg, part, x[None])[0] - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@functools.lru_cache(maxsize=None)
def _cell_hf():
    with open(os.path.join(HERE, "configs", "deepseek-v2-lite.json")) as f:
        return json.load(f)


def test_counts():
    hf = _cell_hf()
    fam = families.load(hf)
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    # top-6 over 16 of 64 held: 1.5 held experts a token
    call = fam.gmm_call(hf, 1000)
    assert call["flops"] == 2.0 * 1500 * d * f
    assert fam.gmm_calls_per_prompt(hf) == 18
    # a step of n rows reaches held * (1 - (1 - 6/64)^n) experts
    one = fam.decode_bytes(hf, [0])
    assert fam.decode_bytes(hf, [0, 0]) - one == pytest.approx(
        6 * 3 * d * f * 2 * 16 * 6 / 64 * (1 - 6 / 64)
        + 7 * 576 * 2, rel=1e-9)
    # enough rows reach every held expert: every weight once
    assert fam.decode_bytes(hf, [0] * 4096) == pytest.approx(
        fam.weight_bytes(hf) + 4096 * 7 * 576 * 2, rel=1e-12)
    assert flops.prefill_flops(hf, 1024) > 2 * 1024 * fam.matmul_params(
        hf) - 2 * 1024 * fam.head_params(hf)
    with pytest.raises(NotImplementedError):
        flops.flash_call(hf, 1, 128)


def test_weights_match_the_program_tree():
    hf = _cell_hf()
    program.check_param_shapes(program.model_config(hf), hf)
    n = sum(int(np.prod(s)) for s in weights.shapes(hf).values())
    assert n == 1_518_110_208
