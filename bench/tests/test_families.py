"""Moving dense Qwen3 into ``bench/families/qwen3.py`` changed no number.

``data/qwen3_parent.json`` and ``data/qwen3_parent_logits.npz`` were
recorded on the CPU with the code as it stood before the move, where the
weights, the reference and the counts lived in ``bench/model.py``,
``bench/reference.py`` and ``bench/flops.py`` (the whole-model kernel
counts as ``bench/measure.py`` took them: one layer's call times the
layers). The family has to give the same bits: every weight leaf of a
module (the cells' configurations cut to one layer, to keep it small), the
float32 reference's logits and its float8 control's for one request, and
every count on fixed lengths and chunks."""
import copy
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import model as bmodel

DATA = Path(__file__).parent / "data"
CONFIGS = Path(__file__).parents[1] / "configs"
PARENT = json.loads((DATA / "qwen3_parent.json").read_text())
LOGITS = np.load(DATA / "qwen3_parent_logits.npz")


def _conf(name):
    path = DATA / "tiny.json" if name == "tiny" else CONFIGS / f"{name}.json"
    return bmodel.load(path)


@pytest.mark.parametrize("which", sorted(PARENT["weights"]))
def test_weights_are_the_parents(which):
    name, module = which.split("/")
    conf = copy.deepcopy(_conf(name))
    if name != "tiny":
        conf["num_hidden_layers"] = 1
    params = bmodel.family(conf).init_module(conf, PARENT["seed"], module)
    got = {jax.tree_util.keystr(k): [list(v.shape), str(v.dtype),
                                     hashlib.sha256(np.asarray(v).tobytes())
                                     .hexdigest()]
           for k, v in jax.tree_util.tree_leaves_with_path(params)}
    assert got == PARENT["weights"][which]


@pytest.mark.parametrize("control", [False, True], ids=["reference",
                                                         "control"])
def test_served_logits_are_the_parents(control):
    conf = _conf("tiny")
    fam = bmodel.family(conf)
    r = PARENT["request"]
    base = fam.init_module(conf, r["seed"], conf["deployment"]
                           ["prefill_module"])
    dec = fam.init_module(conf, r["seed"], r["module"])
    with jax.default_matmul_precision("highest"):
        got = fam.served_logits(fam.shapes(conf), base, dec, r["prompt"],
                                r["served"], r["first_token"],
                                control=control)
    want = LOGITS["control" if control else "reference"]
    np.testing.assert_array_equal(np.asarray(got), want)


def _count(name, quantity):
    conf = _conf(name)
    fam = bmodel.family(conf)
    work = (PARENT["lengths"] if quantity in ("decode_step", "paged_decode")
            else [tuple(c) for c in PARENT["chunks"]])
    got = getattr(fam, quantity)(fam.shapes(conf), work)
    return list(got) if isinstance(got, tuple) else got


CELLS = sorted(PARENT["counts"])


@pytest.mark.parametrize("name", CELLS)
def test_decode_step_is_the_parents(name):
    assert _count(name, "decode_step") == PARENT["counts"][name]["decode_step"]


@pytest.mark.parametrize("name", CELLS)
def test_chunk_step_is_the_parents(name):
    assert _count(name, "chunk_step") == PARENT["counts"][name]["chunk_step"]


@pytest.mark.parametrize("name", CELLS)
def test_paged_decode_is_the_parents(name):
    assert (_count(name, "paged_decode")
            == PARENT["counts"][name]["paged_decode"])


@pytest.mark.parametrize("name", CELLS)
def test_flash_prefill_paged_is_the_parents(name):
    assert (_count(name, "flash_prefill_paged")
            == PARENT["counts"][name]["flash_prefill_paged"])
