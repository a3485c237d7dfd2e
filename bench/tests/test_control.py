"""The comparison at a size a test run can hold (the tiny cell): the
control, the reference computed in bfloat16 in the program's place,
fails it.  (That a sound run passes it is ``test_faults``'s.)"""
import jax.numpy as jnp
import pytest

from bench import correct, reference, run
from bench.tests import tiny

SEED = 2 ** 31 + 4242


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny")
    return run.Cell.load(tiny.write_root(root), root)


@pytest.fixture(scope="module")
def ref(cell):
    corpus = cell.family.Corpus(cell.config)
    init, params, books = reference.simulate(
        cell.family, cell.config, cell.traffic, corpus,
        run.testbed_seed(SEED), 1.0)
    return corpus, init, params, books


def test_the_bfloat16_control_fails(cell, ref):
    corpus, init, params, books = ref
    _, ctl, ctl_books = reference.simulate(
        cell.family, cell.config, cell.traffic, corpus,
        run.testbed_seed(SEED), 1.0, dtype=jnp.bfloat16)
    numbers = correct.compare(init, ctl, ctl_books, params, books)
    limits = correct.limits_for(cell.limits, 1.0)
    ok, checks = correct.judge(numbers, limits)
    assert not ok, checks
    assert numbers["tree_dist"] > 10 * limits["tree_dist"]


def test_limits_are_read_per_noise_multiplier():
    spec = {"limits": {"books_diff": 0},
            "by_sigma": {"0.5": {"tree_dist": 0.02},
                         "2.0": {"tree_dist": 0.005}}}
    assert correct.limits_for(spec, 0.5) == {"books_diff": 0,
                                             "tree_dist": 0.02}
    assert correct.limits_for(spec, 2)["tree_dist"] == 0.005
    with pytest.raises(KeyError):
        correct.limits_for(spec, 1.0)
