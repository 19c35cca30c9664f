"""The benchmark's numbers for the dense GQA family, pinned: the weight
tree and its values per seed, every operation and byte count, and the
tiny reference's logits.  The values were read from the harness as it
stood before the family code moved to ``chipbench/families/llama.py``;
any change to them changes what ``smollm-360m.chat`` measures."""

import hashlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, reference, weights
from chipbench.tests import tiny

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (2**31 + 7, 12345)
LENGTHS = (128, 1024, 1536)
POSITIONS = [0, 5, 63, 64, 100, 1535, 2047]
WINDOWS = (None, 64)


def _smollm():
    with open(os.path.join(HERE, "configs", "smollm-360m.json")) as f:
        return json.load(f)


def _tiny():
    return {**_smollm(), **tiny.overrides()["config"]}


CONFIGS = {"tiny": _tiny, "smollm": _smollm}

SHAPES = {
    "tiny": {
        "embed": (256, 64),
        "final_norm/scale": (64,),
        "layers/attn/wk": (2, 64, 32),
        "layers/attn/wo": (2, 64, 64),
        "layers/attn/wq": (2, 64, 64),
        "layers/attn/wv": (2, 64, 32),
        "layers/ln1/scale": (2, 64),
        "layers/ln2/scale": (2, 64),
        "layers/mlp/w_down": (2, 128, 64),
        "layers/mlp/w_gate": (2, 64, 128),
        "layers/mlp/w_up": (2, 64, 128)},
    "smollm": {
        "embed": (49152, 960),
        "final_norm/scale": (960,),
        "layers/attn/wk": (32, 960, 320),
        "layers/attn/wo": (32, 960, 960),
        "layers/attn/wq": (32, 960, 960),
        "layers/attn/wv": (32, 960, 320),
        "layers/ln1/scale": (32, 960),
        "layers/ln2/scale": (32, 960),
        "layers/mlp/w_down": (32, 2560, 960),
        "layers/mlp/w_gate": (32, 960, 2560),
        "layers/mlp/w_up": (32, 960, 2560)},
}

#: sha256 over (path, float32 bytes) of every leaf of ``weights.make``
WEIGHTS = {
    "tiny": {
        SEEDS[0]: "c26e7819a1857a81531646ca71b4e4ab"
                  "3b1639517bcf1e8d3ac59c61e4a96f72",
        SEEDS[1]: "412e84b0ab2006fbd419564f83b26cb6"
                  "d6d8f0aac0c49f2acdaa25dd7c7a0a1c"},
    "smollm": {
        SEEDS[0]: "993fcd3f195b34da58ba731d4d57df5e"
                  "aa88616b6beccdf560028c5a2fff59bf",
        SEEDS[1]: "f6a927dfb1ed335504ee574fc75a8c00"
                  "6bfe170aac08f7852f4e5942b02c9d0a"},
}

#: per window: the counts that do not depend on the prompt length, then
#: attn_flops, prefill_flops and flash_call(batch 2) at each of LENGTHS
COUNTS = {
    ("tiny", None): {
        "layer_matmul_params": 36864, "head_params": 16384,
        "matmul_params": 90112, "weight_bytes": 180864,
        "decode_bytes": 1159040,
        "attn_flops": (2113536, 134348800, 302186496),
        "prefill_flops": (23134208, 419725312, 830898176),
        "flash_call": ((4227072, 98304), (268697600, 786432),
                       (604372992, 1179648))},
    ("tiny", 64): {
        "layer_matmul_params": 36864, "head_params": 16384,
        "matmul_params": 90112, "weight_bytes": 180864,
        "decode_bytes": 265600,
        "attn_flops": (1581056, 16261120, 24649728),
        "prefill_flops": (22069248, 183549952, 275824640),
        "flash_call": ((3162112, 98304), (32522240, 786432),
                       (49299456, 1179648))},
    ("smollm", None): {
        "layer_matmul_params": 9830400, "head_params": 47185920,
        "matmul_params": 361758720, "weight_bytes": 723642240,
        "decode_bytes": 880150400,
        "attn_flops": (31703040, 2015232000, 4532797440),
        "prefill_flops": (81639505920, 708826890240, 1111511531520),
        "flash_call": ((63406080, 1310720), (4030464000, 10485760),
                       (9065594880, 15728640))},
    ("smollm", 64): {
        "layer_matmul_params": 9830400, "head_params": 47185920,
        "matmul_params": 361758720, "weight_bytes": 723642240,
        "decode_bytes": 737200000,
        "attn_flops": (23715840, 243916800, 369745920),
        "prefill_flops": (81383915520, 652144803840, 978293882880),
        "flash_call": ((47431680, 1310720), (487833600, 10485760),
                       (739491840, 15728640))},
}

#: sha256 of the tiny reference's float32 logits on TOKENS, at f32 and at
#: the fp8 control, without a window and with a 16-token one
TOKENS = np.random.default_rng(3).integers(0, 256, size=40).astype(np.int32)
LOGITS = {
    None: ("dafbbd8e3c81c5680990d00439cbe0ceb91569dfab2d2d8ee6c02aee10a7a16d",
           "839e60ff1a562443d428e8470e3bd0e4a039fcaca290dc94b00da7e9a8f34e8d"),
    16: ("bd8cece3caf6900514657139f6888f3437c73d7947e46b6ddd3ae7429d9c1121",
         "b6a1a4b20f2d1baa0973ee2e747c16ed78e920c19256e6056f1d72ba88a377d7"),
}


def _tree_hash(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in sorted(weights.flatten(tree).items()):
        h.update(path.encode())
        h.update(np.asarray(leaf.astype(jnp.float32)).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_weight_shapes(name):
    assert weights.shapes(CONFIGS[name]()) == SHAPES[name]


@pytest.mark.parametrize("name,seed", [(n, s) for n in sorted(CONFIGS)
                                       for s in SEEDS])
def test_weights_per_seed(name, seed):
    assert _tree_hash(weights.make(CONFIGS[name](), seed)) == \
        WEIGHTS[name][seed]


@pytest.mark.parametrize("name,window", sorted(COUNTS, key=str))
def test_counts(name, window):
    hf = dict(CONFIGS[name](), sliding_window=window)
    want = COUNTS[(name, window)]
    got = {k: getattr(flops, k)(hf) for k in (
        "layer_matmul_params", "head_params", "matmul_params",
        "weight_bytes")}
    got["decode_bytes"] = flops.decode_bytes(hf, POSITIONS)
    got["attn_flops"] = tuple(flops.attn_flops(hf, s) for s in LENGTHS)
    got["prefill_flops"] = tuple(flops.prefill_flops(hf, s) for s in LENGTHS)
    got["flash_call"] = tuple(
        (c["flops"], c["bytes"]) for c in (flops.flash_call(hf, 2, s)
                                           for s in LENGTHS))
    assert got == want


@pytest.mark.parametrize("window", sorted(LOGITS, key=str))
def test_reference_logits(window):
    hf = dict(_tiny(), sliding_window=window)
    params = reference.to_f32(weights.make(hf, SEEDS[0]))
    got = tuple(hashlib.sha256(np.asarray(reference.logits(
        hf, params, jnp.asarray(TOKENS), p)).tobytes()).hexdigest()
        for p in ("f32", "fp8"))
    assert got == LOGITS[window]
