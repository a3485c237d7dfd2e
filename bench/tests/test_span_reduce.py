"""The span reduction (``bench/span_reduce.py``) on a trace recorded on
one TPU v5e: two short experiments of ``tiny.py``'s cell (noise 0.5 and
1.0, 6 updates each, eval every 3: 3 cohorts and 2 evals each) through
one ``Session``, each inside a ``bench.session_run`` span, so the trace
holds the program's ``fl.*`` spans beside the benchmark's.  The HLO
protos and every op statistic but ``tf_op`` (the named scope) were
stripped from the recorded file; no reduction reads them."""
from pathlib import Path

import pytest

from bench import span_reduce, trace_reduce

DATA = Path(__file__).parent / "data"
PROBE_FL = DATA / "probe_fl.xplane.pb"
PROBE = DATA / "probe.xplane.pb"        # bench.* spans only


@pytest.fixture(scope="module")
def red():
    return span_reduce.reduce_file(str(PROBE_FL))


@pytest.mark.parametrize("trace", [PROBE_FL, PROBE], ids=["fl", "bench"])
def test_window_and_busy_are_trace_reduces(trace):
    ours = span_reduce.reduce_file(str(trace))
    theirs = trace_reduce.reduce_file(str(trace))
    assert (ours.window_s, ours.busy_s) == (theirs.window_s, theirs.busy_s)
    assert 0 < ours.busy_s < ours.window_s


@pytest.mark.parametrize("trace", [PROBE_FL, PROBE], ids=["fl", "bench"])
def test_idle_by_span_sums_to_the_window_less_busy(trace):
    r = span_reduce.reduce_file(str(trace))
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-9)


def test_the_program_spans_are_counted(red):
    counts = {n: c for n, (c, _) in red.spans.items()}
    assert counts["fl.session.run"] == 2
    assert counts["fl.cohort"] == counts["fl.stage"] == 6
    assert counts["fl.eval"] == 4
    assert all(n.startswith("fl.") for n in counts)
    assert all(0 < s <= red.window_s for _, s in red.spans.values())


def test_idle_goes_to_program_spans(red):
    assert {"fl.eval", "fl.stage", "fl.submit"} <= set(red.idle_by_span)
    program = sum(s for n, s in red.idle_by_span.items()
                  if n.startswith("fl.") and n != "fl.session.run")
    assert program > 0.9 * (red.window_s - red.busy_s)
    # a span's idle, at any depth, holds the idle of what opens inside it
    assert red.idle_within["fl.session.run"] >= sum(
        s for n, s in red.idle_by_span.items()
        if n.startswith("fl.") and n != "fl.session.run")


def test_the_longest_gaps_carry_program_span_names(red):
    names = [n for n, _ in red.gaps]
    assert len(names) == trace_reduce.TOP
    assert sum(n.startswith("fl.") for n in names) >= 8
    assert "fl.submit" in names
    secs = [s for _, s in red.gaps]
    assert secs == sorted(secs, reverse=True)


@pytest.mark.parametrize("metric", sorted(span_reduce.METRICS))
def test_each_metric_reads_a_number(red, metric):
    value = span_reduce.METRICS[metric](red)
    assert isinstance(value, float) and 0 < value < 1e3 * red.window_s


@pytest.mark.parametrize("metric", sorted(span_reduce.METRICS))
def test_each_metric_is_none_without_program_spans(metric):
    assert span_reduce.METRICS[metric](
        span_reduce.reduce_file(str(PROBE))) is None


def test_innermost_names_each_piece_by_the_shortest_open_span():
    spans = [(0, 10, "bench.run"), (2, 6, "fl.cohort"), (3, 4, "fl.stage"),
             (12, 14, "fl.eval")]
    assert span_reduce._innermost(spans, 0, 15) == [
        (0, 2, "bench.run"), (2, 3, "fl.cohort"), (3, 4, "fl.stage"),
        (4, 6, "fl.cohort"), (6, 10, "bench.run"), (10, 12, "none"),
        (12, 14, "fl.eval"), (14, 15, "none")]
    pieces = span_reduce._innermost(spans, 0, 15)
    idle = [(1, 3.5), (11, 13)]
    assert [s * 1e9 for _, s in span_reduce._overlap(pieces, idle)] == \
        pytest.approx([1, 1, 0.5, 0, 0, 1, 1, 0])
