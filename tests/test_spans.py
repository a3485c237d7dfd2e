"""The program's host spans (``repro.core.spans``) in a real profiler
trace: tiny experiments run through ``Session.run`` under
``jax.profiler.trace`` on the CPU, and the trace's ``fl.*`` host events
are read back with ``ProfileData`` and held to the engine's counters."""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.api import ExperimentSpec, RunBudget, Session, StrategySpec
from repro.core.spans import SPAN_NAMES
from repro.core.testbed import TestbedConfig
from repro.data.synthetic_ser import SERDataConfig
from repro.engine import EngineConfig, StoreConfig
from repro.models.ser_cnn import SERConfig

# reading an event's stats makes the profiler's bindings warn once
pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type event_stats has no __module__:DeprecationWarning")

DIMS = dict(time_frames=12, n_mels=12)
TB = TestbedConfig(
    workload="ser_linear", use_dp=True, sigma=1.0, batch_size=16,
    num_clients=4, data=SERDataConfig(n_total=144, **DIMS),
    model=SERConfig(channels1=8, channels2=16, fc_dim=16, **DIMS), seed=5)
ASYNC = StrategySpec("fedasync", alpha=0.4)
WINDOW = dict(staleness_window=45.0, max_cohort=2)
SCENARIOS = {
    "fedasync": ExperimentSpec(
        testbed=TB, strategy=ASYNC, engine=EngineConfig(**WINDOW),
        run=RunBudget(max_updates=12, eval_every=4)),
    "pipelined": ExperimentSpec(
        testbed=TB, strategy=ASYNC,
        engine=EngineConfig(pipeline_depth=2, **WINDOW),
        run=RunBudget(max_updates=12, eval_every=6)),
    "store": ExperimentSpec(
        testbed=TB, strategy=ASYNC,
        engine=EngineConfig(store=StoreConfig(hot_slots=2, lookahead=1),
                            **WINDOW),
        run=RunBudget(max_updates=12, eval_every=4)),
    "fedavg": ExperimentSpec(
        testbed=TB, strategy=StrategySpec("fedavg"),
        engine=EngineConfig(max_cohort=2), run=RunBudget(rounds=3)),
}
# one of each per run: Session.run's set-up spans and the loop's ends
ONCE = ("fl.session.run", "fl.session.testbed", "fl.session.partitions",
        "fl.session.runner", "fl.startup", "fl.finish")


@dataclasses.dataclass
class Traced:
    params: object
    log: object
    events: list        # (name, start_ns, end_ns, thread, stats)

    def named(self, name):
        return [e for e in self.events if e[0] == name]


def _fl_events(directory) -> list:
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("fl."):
                    out.append((ev.name, ev.start_ns, ev.end_ns,
                                (plane.name, line.name), dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Each scenario run once, in a fresh Session, inside its own trace."""
    cache = {}

    def get(name) -> Traced:
        if name not in cache:
            d = tmp_path_factory.mktemp(f"trace_{name}")
            with jax.profiler.trace(str(d)):
                params, log = Session().run(SCENARIOS[name])
                params = jax.device_get(params)
            cache[name] = Traced(params, log, _fl_events(d))
        return cache[name]
    return get


@pytest.mark.parametrize("name", SCENARIOS)
def test_every_span_name_is_registered(traced, name):
    seen = {e[0] for e in traced(name).events}
    assert seen <= set(SPAN_NAMES), seen - set(SPAN_NAMES)
    assert {"fl.stage", "fl.submit", "fl.merge", "fl.books",
            "fl.eval"} <= seen


@pytest.mark.parametrize("name", SCENARIOS)
def test_set_up_and_finish_spans_open_once_per_run(traced, name):
    t = traced(name)
    assert {n: len(t.named(n)) for n in ONCE} == {n: 1 for n in ONCE}
    run, = t.named("fl.session.run")
    assert all(run[1] <= e[1] and e[2] <= run[2] for e in t.events)


@pytest.mark.parametrize("name", SCENARIOS)
def test_step_spans_count_the_loop(traced, name):
    t = traced(name)
    if name == "fedavg":
        rounds = t.named("fl.round")
        assert [e[4]["step_num"] for e in rounds] == [1, 2, 3]
        assert not t.named("fl.cohort")
        assert len(t.named("fl.stage")) == t.log.engine_stats["cohorts"]
    else:
        cohorts = t.named("fl.cohort")
        assert len(cohorts) == t.log.engine_stats["cohorts"]
        assert [e[4]["step_num"] for e in cohorts] == list(
            range(len(cohorts)))
        assert len(t.named("fl.stage")) == len(cohorts)


@pytest.mark.parametrize("name", SCENARIOS)
def test_eval_and_drain_spans_count_their_events(traced, name):
    t = traced(name)
    assert len(t.named("fl.eval")) == len(t.log.times) > 0
    assert len(t.named("fl.drain")) == t.log.engine_stats["drain_waits"]
    if name == "pipelined":
        assert t.log.engine_stats["drain_waits"] > 0


@pytest.mark.parametrize("name", SCENARIOS)
def test_cohort_phases_nest_in_a_step_span_on_its_thread(traced, name):
    t = traced(name)
    steps = t.named("fl.round" if name == "fedavg" else "fl.cohort")
    for phase in ("fl.stage", "fl.submit", "fl.merge", "fl.books",
                  "fl.eval"):
        for ev in t.named(phase):
            assert any(s[3] == ev[3] and s[1] <= ev[1] and ev[2] <= s[2]
                       for s in steps), (phase, ev)


def test_store_spans_count_the_store_traffic(traced):
    t = traced("store")
    st = t.log.engine_stats
    assert len(t.named("fl.store.acquire")) == st["cohorts"]
    assert len(t.named("fl.store.spill")) == st["store_sync_reads"] > 0
    assert t.named("fl.store.load") and t.named("fl.prefetch")


@pytest.mark.parametrize("name", SCENARIOS)
def test_the_profiler_leaves_params_and_books_unchanged(traced, name):
    t = traced(name)
    params, log = Session().run(SCENARIOS[name])
    for a, b in zip(jax.tree_util.tree_leaves(t.params),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for fld in ("times", "global_acc", "server_version", "update_counts",
                "influence", "staleness", "eps_trajectory", "local_acc",
                "cohort_sizes", "engine_stats"):
        assert getattr(t.log, fld) == getattr(log, fld), fld
