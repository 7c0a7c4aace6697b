"""The gpt2 family is the parent's code, moved: at the tiny sizes its seeded
tree, its reference's losses (float32, float8; Adam, momentum SGD) and its
served logits for one prompt are the parent's to the last bit
(`recorded_gpt2.json`, taken from the parent before anything moved)."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

import tiny
from conftest import BENCH
from lib import harness, train, weights

with open(os.path.join(BENCH, "tests", "recorded_gpt2.json")) as f:
    RECORDED = json.load(f)["seeds"]
MODEL = tiny.model("gpt2")


@pytest.fixture(scope="module")
def gpt2():
    return harness.load_family("gpt2")


def hexes(xs):
    return [float(x).hex() for x in xs]


@pytest.mark.parametrize("seed", sorted(RECORDED, key=int))
def test_the_seeded_tree_and_batches_are_the_parents(gpt2, seed):
    want = RECORDED[seed]
    flat, _ = jax.tree.flatten_with_path(gpt2.weights.make(int(seed), MODEL))
    h = hashlib.sha256()
    for path, x in flat:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == want["tree_sha256"]
    tok, tgt = weights.make_batch_fn(
        int(seed), batch=4, seq=32, vocab=gpt2.weights.vocab(MODEL))(0)
    assert hashlib.sha256(np.asarray(tok).tobytes() + np.asarray(
        tgt).tobytes()).hexdigest() == want["batch0_sha256"]


@pytest.mark.parametrize("seed", sorted(RECORDED, key=int))
@pytest.mark.parametrize("case", ["adam", "sgd", "fp8"])
def test_the_references_first_steps_are_the_parents(gpt2, seed, case):
    want = RECORDED[seed]
    tr = dict(tiny.TRAIN, **({"optimizer": "sgd", "lr": 0.01}
                             if case == "sgd" else {}))
    bf = weights.make_batch_fn(int(seed), batch=4, seq=32, vocab=101)
    ref = train.reference_steps(int(seed), gpt2, MODEL, tr, bf,
                                "fp8" if case == "fp8" else "f32")
    assert hexes(ref["losses"]) == want["losses_" + case]
    if case != "fp8":
        assert float(ref["grad"]["embed"]).hex() == want["grad_embed_" + case]
        assert float(ref["change"]["head"]).hex() == want[
            "change_head_" + case]


@pytest.mark.parametrize("seed", sorted(RECORDED, key=int))
def test_the_served_logits_are_the_parents(gpt2, seed):
    want = RECORDED[seed]
    seq = np.asarray([want["served_prompt"]], np.int32)
    rows = np.asarray([want["served_rows"]], np.int32)
    lg = gpt2.reference.served_logits(int(seed), MODEL, seq, rows, "f32")
    assert lg.shape == (1, len(want["served_rows"]), 101)
    assert hexes(lg[0, 0, :4]) == want["served_logits_row0_first4"]
    assert hashlib.sha256(lg[0].tobytes()).hexdigest() == want[
        "served_logits_sha256"]
    assert lg[0].argmax(-1).tolist() == want["served_argmax"]
