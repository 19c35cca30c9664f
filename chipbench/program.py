"""What the benchmark takes from the program: its model configuration,
built from a configuration file, and the shapes of its parameters.

The configuration file is what runs: every published number in it
overrides the registry entry of the same architecture, and the harness
refuses a program whose parameter tree differs from ``weights.shapes``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

#: configuration-file key -> ModelConfig field
FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "head_dim": "head_dim",
    "sliding_window": "window",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "hidden_act": "activation",
    "torch_dtype": "param_dtype",
}


def model_config(hf: Dict):
    """The program's ``ModelConfig`` for the configuration file ``hf``."""
    from repro.configs import get_config

    cfg = get_config(hf["arch"])
    changes = {field: hf[key] for key, field in FIELDS.items()}
    changes["compute_dtype"] = hf["torch_dtype"]
    changes["gated_mlp"] = True
    return dataclasses.replace(cfg, **changes)


def check_param_shapes(cfg, hf: Dict) -> None:
    """Raise unless the program's parameter tree is ``weights.shapes``."""
    import functools

    import jax

    from repro.models.model import init_params

    from chipbench import weights

    tree = jax.eval_shape(functools.partial(init_params, cfg),
                          jax.random.PRNGKey(0))
    got = {k: tuple(v.shape) for k, v in weights.flatten(tree).items()}
    want = weights.shapes(hf)
    if got != want:
        raise RuntimeError(f"program parameter tree {got} differs from the "
                           f"benchmark's {want}")


def describe(cfg) -> Dict[str, str]:
    """Implementation choices the program made at its defaults."""
    from repro.models.layers import resolve_attn_impl

    return {"attn_impl": resolve_attn_impl(cfg), "remat": cfg.remat,
            "param_dtype": cfg.param_dtype,
            "compute_dtype": cfg.compute_dtype}
