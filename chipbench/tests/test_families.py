"""A family is added by a file: the harness finds it by ``model_type``,
refuses an unknown one before any weight is made, and its generic modules
name no key of one family."""

import os
import shutil

import pytest

from chipbench import families, run, weights
from chipbench.tests import tiny

SEED = 2**31 + 4243
#: keys of the dense GQA family's configuration that no generic module reads
LLAMA_ONLY = ("num_key_value_heads", "head_dim", "intermediate_size",
              "tie_word_embeddings", "sliding_window")
GENERIC = ("program.py", "weights.py", "reference.py", "flops.py",
           "serve.py", "run.py")


def _run(model_type):
    over = tiny.overrides()
    over["config"] = dict(over["config"], model_type=model_type)
    return run.execute("smollm-360m.chat", SEED, 1.0, False,
                       require_chip=False, overrides=over)


def test_family_added_as_a_file(tmp_path, monkeypatch):
    shutil.copy(os.path.join(families.DIR, "llama.py"),
                tmp_path / "toyllama.py")
    monkeypatch.setattr(families, "DIR", str(tmp_path))
    assert families.known() == ["toyllama"]
    out = _run("toyllama")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert families.load(out["_record"]["hf"]).__file__ == str(
        tmp_path / "toyllama.py")


def test_unknown_family_is_refused_before_weights(monkeypatch):
    def made(*a, **kw):
        raise AssertionError("weights made for an unknown family")

    monkeypatch.setattr(weights, "make", made)
    with pytest.raises(ValueError) as err:
        _run("nosuchfamily")
    msg = str(err.value)
    assert os.path.join(families.DIR, "nosuchfamily.py") in msg
    assert "llama" in msg


@pytest.mark.parametrize("name", GENERIC)
def test_generic_modules_name_no_family_key(name):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, name)) as f:
        src = f.read()
    assert [k for k in LLAMA_ONLY if k in src] == []
