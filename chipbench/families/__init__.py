"""Architecture families: all that the benchmark knows of one family of
models, in one file each, chosen by a configuration file's ``model_type``.

``chipbench/families/<model_type>.py`` provides, for a configuration dict
``hf`` of that family:

- ``program_changes(hf)``: the program's ``ModelConfig`` fields that the
  file sets (``program.model_config`` applies them to the registry entry);
- ``shapes(hf)``: ``{path: shape}`` of every parameter leaf, as the
  program's tree holds them, and ``std(hf, path, shape)``: the spread of a
  random leaf that is not a norm scale (``weights.make``);
- ``logits(hf, params, tokens, precision)``: the plain float32 reference
  forward, every matrix product through ``reference._mm`` so that the
  float8 control reaches it (``reference.logits``);
- the counts ``flops`` dispatches to: ``layer_matmul_params``,
  ``head_params``, ``matmul_params``, ``weight_bytes``, ``attn_flops``,
  ``prefill_flops``, ``flash_call`` and ``decode_bytes``.

A family file imports nothing of the program.  Adding a configuration of
a new family adds its file here; no other file of the harness changes.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
from typing import Dict

#: where family files are looked for (tests point it elsewhere)
DIR = os.path.dirname(os.path.abspath(__file__))
#: the family of a configuration dict that names no ``model_type``
DEFAULT = "llama"


def known() -> list:
    """The families that ``DIR`` holds."""
    return sorted(f[:-3] for f in os.listdir(DIR)
                  if f.endswith(".py") and not f.startswith("_"))


@functools.lru_cache(maxsize=None)
def _load_path(path: str, model_type: str):
    spec = importlib.util.spec_from_file_location(
        "chipbench_family_" + model_type, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(hf: Dict):
    """The family module of configuration ``hf``; an unknown
    ``model_type`` is an error that names the file looked for."""
    model_type = hf.get("model_type", DEFAULT)
    path = os.path.join(DIR, f"{model_type}.py")
    if not model_type.isidentifier() or not os.path.isfile(path):
        raise ValueError(f"no family for model_type {model_type!r}: {path} "
                         f"does not exist (known: {known()})")
    return _load_path(path, model_type)


def freeze(hf: Dict) -> str:
    """``hf`` as the key of a cache of compiled functions, nested groups
    (such as a rope scaling) included; ``thaw`` gives the dict back."""
    return json.dumps(hf, sort_keys=True)


def thaw(key: str) -> Dict:
    return json.loads(key)
