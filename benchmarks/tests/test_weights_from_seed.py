"""The seed's weights did not move when the tree became the family's: the
two configurations' canonical trees at their rehearsal sizes hash to what the
checkout before the move (commit d289eba, PR 27) gave for the same seeds.
The order of the draws over the sorted keys is part of the contract
(``harness/weights.py``)."""

import hashlib
import os

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import runtime, weights

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")

# sha256 over the sorted keys of (key, dtype, shape, bytes), from
# weights.make_weights(runtime.model_dims(config), seed, dtype) there
PARENT = {
    ("bert-base-seq512", 0):
        "ad4a24c2efae44a75c36cea3370728a0abeb041adf71ad1c9d4fc30e99d4e943",
    ("bert-base-seq512", 1):
        "f3fdae6546f7a3217102f9d13bc1508de5a6c01492ed2cfeb8767e9ebe01b6a0",
    ("bert-base-seq512", 2**31 + 5):
        "46de02400fbc1027853aaacedb896b93ff16c6633850f734d6c322e551246759",
    ("gpt2s-chat-closed128", 0):
        "e96edb3944f698d52324a541a39a6641863cb29bb98e58e6a69e728228dccfe3",
    ("gpt2s-chat-closed128", 1):
        "852e63018d0d016143d65be174ebf9500db949828d1aa1d21fee9d48ef7f3e9f",
    ("gpt2s-chat-closed128", 2**31 + 5):
        "9cf9ffe5c4e0e81612cec3eae8b0b4d9925e24d6ae11c4a136a56f0d21d72efc",
}


def _tree(data, cell, seed):
    _, config, _, family_file = bench_run.load_cell(data, cell, True)
    family = runtime.load_family(family_file)
    dims = family.dims(config)
    return family, dims, config, weights.make_weights(
        family, dims, seed, config["dtype"])


@pytest.mark.parametrize("cell, seed", sorted(PARENT))
def test_same_seed_same_weights_as_before_the_move(cell, seed):
    *_, w = _tree(BENCH, cell, seed)
    h = hashlib.sha256()
    for k in sorted(w):
        a = np.asarray(w[k])
        for part in (k.encode(), str(a.dtype).encode(),
                     str(a.shape).encode(), a.tobytes()):
            h.update(part)
    assert h.hexdigest() == PARENT[cell, seed]


def test_groups_stack_on_their_own_axes_and_cut_to_the_programs_tree():
    """The fixture's LSTM has two groups (the first layer, whose input is the
    vocabulary, and the two that follow): each key of a group is stacked
    ``count`` times, and the layout's ``index`` sums name the layers."""
    family, dims, config, w = _tree(DATA, "tiny-lstm-closed", 7)
    assert family.groups(dims) == {"first": 1, "rest": 2}
    assert w["first_w"].shape == (1, 40, 96) and w["w"].shape == (2, 24, 96)
    assert w["head_w"].shape == (24, 40)
    assert weights.stacked_keys(family, dims) == {
        "first_w", "first_rw", "first_b", "first_p", "w", "rw", "b", "p"}
    tree = weights.program_tree(family, dims, w, config["layout"])
    assert sorted(tree) == ["layer_0", "layer_1", "layer_2", "layer_3"]
    np.testing.assert_array_equal(tree["layer_2"]["RW"], w["rw"][1])
    np.testing.assert_array_equal(tree["layer_0"]["W"], w["first_w"][0])
    np.testing.assert_array_equal(tree["layer_3"]["b"], w["head_b"])
    ones = {layer: {p: 1.0 for p in params} for layer, params in tree.items()}
    names = weights.canonical_names(family, dims, ones, config["layout"])
    assert set(names) == set(weights.stacked_names(
        {k: np.ones(v.shape[0]) if k in weights.stacked_keys(family, dims)
         else 1.0 for k, v in w.items()}, weights.stacked_keys(family, dims)))
    assert "rw.1" in names and "first_p.0" in names and "head_b" in names
