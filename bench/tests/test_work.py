"""The analytic counts against hand-computed values."""
import json

import jax
import pytest
from jax.flatten_util import ravel_pytree

from fedbench import work
from fedbench.registry import BENCH, load_module


def config(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return cfg, load_module(BENCH / cfg["reference"], f"ref_{name}")


@pytest.mark.parametrize("name, params, per_sample", [
    # conv1 28*28*32*25 + conv2 14*14*64*800 + fc 3136*2048 + 2048*62
    # multiply-adds = 17,211,904; x 2 = 34.42 MFLOP forward, x 3 trained
    ("femnist_cnn", 6_603_710, 103_271_424),
    # 80 steps x ((8 + 256) + (256 + 256)) x 1024 + 256 * 82
    # multiply-adds = 63,590,912; x 2 = 127.2 MFLOP forward, x 3 trained
    ("shakespeare_lstm", 818_402, 381_545_472),
])
def test_params_and_flops(name, params, per_sample):
    cfg, ref = config(name)
    assert ref.param_count(cfg) == params == cfg["params"]
    assert work.train_flops(ref.forward_flops(cfg), 1) == per_sample
    tree = ref.init(jax.random.PRNGKey(0), cfg)
    assert ravel_pytree(tree)[0].size == params


@pytest.mark.parametrize("name", ["femnist_cnn", "shakespeare_lstm"])
def test_reference_layout_matches_the_program(name):
    """The benchmark's weights go into the program as its initial params:
    same tree, same shapes, same count as the program's own init."""
    import importlib
    cfg, ref = config(name)
    mod, fn = cfg["program"]["factory"].split(":")
    model = getattr(importlib.import_module(mod), fn)(
        **cfg["program"]["kwargs"])
    mine = ref.init(jax.random.PRNGKey(0), cfg)
    theirs = model.init(jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    assert shapes(mine) == shapes(theirs)


def test_merge_work_by_hand():
    # K = 14 updates of the FEMNIST CNN
    ops, nbytes = work.merge_work(14, 6_603_710)
    assert ops == 2 * 14 * 6_603_710 == 184_903_880
    assert nbytes == (14 * 6_603_710 + 6_603_710 + 14) * 4 == 396_222_656
    t, bound = work.least_time(ops, nbytes, 197e12, 819e9)
    assert bound == "memory"
    assert t == pytest.approx(396_222_656 / 819e9)
    t, bound = work.least_time(4e12, 1e9, 197e12, 819e9)
    assert bound == "compute" and t == pytest.approx(4e12 / 197e12)
