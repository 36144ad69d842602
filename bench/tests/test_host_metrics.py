"""Readers of the host-path sub-stage spans and step counters."""

from types import SimpleNamespace

import pytest

from bench import measures

SPANS = {"extract_pull_ms": "extract_pull", "ell_refresh_ms": "ell_refresh",
         "compile_ms": "compile", "gc_ms": "gc"}
COUNTS = {"d2h_mb": "d2h_bytes", "h2d_mb": "h2d_bytes"}


def _view(steps):
    return SimpleNamespace(steps=steps)


def _step(stage, counts=None):
    stats = SimpleNamespace(stage_s=stage)
    if counts is not None:
        stats.counts = counts
    return SimpleNamespace(stats=stats)


@pytest.mark.parametrize("cell", ["steady", "catchup"])
@pytest.mark.parametrize("metric", sorted(SPANS))
def test_span_readers_mean_per_step(metric, cell):
    key = SPANS[metric]
    v = _view([_step({"apply": 1.0, key: 0.010}),
               _step({"apply": 1.0, key: 0.030})])
    assert measures.load_reader(f"{metric}.{cell}")(v) == pytest.approx(20.0)


@pytest.mark.parametrize("metric", ["extract_pull_ms", "compile_ms", "gc_ms"])
def test_span_readers_leave_out_a_program_without_the_stage(metric):
    # traced steps of a program that never sets the key (an older tree)
    v = _view([_step({"apply": 0.2, "extract": 0.1})])
    assert measures.load_reader(f"{metric}.steady")(v) is None
    assert measures.load_reader(f"{metric}.steady")(_view([_step({})])) \
        is None


@pytest.mark.parametrize("cell", ["steady", "catchup"])
@pytest.mark.parametrize("metric", sorted(COUNTS))
def test_counter_readers_mean_megabytes(metric, cell):
    key = COUNTS[metric]
    v = _view([_step({"apply": 1.0}, {key: 76_800_000}),
               _step({"apply": 1.0}, {key: 78_800_000})])
    assert measures.load_reader(f"{metric}.{cell}")(v) == pytest.approx(77.8)


@pytest.mark.parametrize("metric", sorted(COUNTS))
def test_counter_readers_leave_out_a_program_without_counts(metric):
    # no ``counts`` attribute at all (an older tree), or tracing off
    assert measures.load_reader(f"{metric}.steady")(
        _view([_step({"apply": 1.0})])) is None
    assert measures.load_reader(f"{metric}.steady")(
        _view([_step({}, {})])) is None
