"""A whole benchmark run of the tiny cell, with the look for a chip
skipped and the timed path broken underneath: ``correct`` comes out
false for each fault the cell can have.  (The cell runs on one chip, so
there is no exchange between chips to leave out.)"""
import pytest

from bench import run
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny")
    tiny.write_root(root)
    return root


def bench_run(root, seed):
    return run.run(["--workload", "tiny", "--seed", str(seed),
                    "--seconds", "0.5", "--trace", "0"],
                   require_chip=False, root=root)


@pytest.fixture
def fresh_steps():
    from repro.engine.cohort_step import invalidate_step_cache
    invalidate_step_cache()
    yield
    invalidate_step_cache()


def unchanged(monkeypatch):
    """The cohort step returns every member's params as it got them."""
    from repro.engine.engine import CohortRunner
    submit = CohortRunner.submit_cohort

    def broken(self, staged):
        submit(self, staged)
        return self._gather(self._arena_params, staged.slots)

    monkeypatch.setattr(CohortRunner, "submit_cohort", broken)


def half_batch(monkeypatch):
    """Each DP step leaves out the second half of its batch and takes the
    mean over the rest."""
    import jax
    from repro.engine import cohort_step
    real = cohort_step.dp_mean_gradient

    def broken(loss_fn, params, batch, key, cfg, **kw):
        half = jax.tree_util.tree_map(lambda l: l[: l.shape[0] // 2], batch)
        return real(loss_fn, params, half, key, cfg, **kw)

    monkeypatch.setattr(cohort_step, "dp_mean_gradient", broken)


def answer(monkeypatch):
    """Every epsilon the accountant hands the books is off by one part in
    10**6."""
    from repro.engine.engine import CohortRunner
    eps = CohortRunner._client_epsilon
    monkeypatch.setattr(CohortRunner, "_client_epsilon",
                        lambda self, c, s: eps(self, c, s) * (1 + 1e-6))


def test_a_sound_run_is_correct(root, fresh_steps):
    res = bench_run(root, 2 ** 31 + 11)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [unchanged, half_batch, answer])
def test_a_broken_timed_path_is_not_correct(root, fresh_steps, monkeypatch,
                                            fault):
    fault(monkeypatch)
    res = bench_run(root, 2 ** 31 + 12)
    assert not res["correct"], res["checks"]
