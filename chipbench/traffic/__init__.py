"""Traffic generators, found by name.

A cell's file names its generator under ``traffic.generator``; the
harness loads ``chipbench/traffic/<generator>.py`` and drives it only
through this interface:

- ``generate(params, seed, seconds, vocab_size)``: the requests of the
  preroll and the window, as ``Arrival``s in due order;
- ``length_set(params)``: every prompt length it can produce, the shapes
  the set-up warms;
- ``offered_tokens_per_s(params)``: the output tokens per second offered.

``params`` is the cell's ``traffic`` object.  A new kind of traffic is a
new file here and a cell that names it; no other file changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os
import re
import sys

import numpy as np

DIR = os.path.dirname(os.path.abspath(__file__))
_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


@dataclasses.dataclass
class Arrival:
    index: int
    segment: str          # "preroll" | "window"
    due: float            # seconds after the traffic clock starts
    prompt: np.ndarray    # (prompt_len,) int32
    out_len: int


def load(params):
    """The generator module that ``params["generator"]`` names."""
    name = params["generator"]
    if not _NAME.match(name):
        raise ValueError(f"bad traffic generator name {name!r}")
    path = os.path.join(DIR, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no traffic generator {path}")
    key = f"chipbench_traffic_{name}"
    if key in sys.modules and sys.modules[key].__file__ == path:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod
