"""Latency and throughput arithmetic of the metric readers."""

from types import SimpleNamespace

import numpy as np
import pytest

from bench import measures


def _view(**kw):
    base = dict(e2e_s=np.array([]), queue_wait_s=np.array([]),
                late_s=np.array([]), steps=[], trace=None, roofline=None,
                seconds=10.0, rate_events=0, rate_s=0.0, setup_s=1.0,
                compiles=0)
    base.update(kw)
    return SimpleNamespace(**base)


def _step(**stage):
    return SimpleNamespace(stats=SimpleNamespace(stage_s=stage))


def test_latency_percentiles_over_the_whole_population():
    e2e = np.arange(1, 101, dtype=float) / 1000.0   # 1..100 ms
    v = _view(e2e_s=e2e)
    assert measures.load_reader("delta_latency_p50_ms")(v) == \
        pytest.approx(50.5)
    assert measures.load_reader("delta_latency_p95_ms")(v) == \
        pytest.approx(95.05)
    assert measures.load_reader("delta_latency_p50_ms")(_view()) is None


def test_events_per_s_is_whole_step_work_over_its_time():
    v = _view(rate_events=4352, rate_s=29.0)
    assert measures.load_reader("events_per_s")(v) == pytest.approx(150.069,
                                                                    rel=1e-4)
    assert measures.load_reader("events_per_s")(_view()) is None


def test_stage_means_per_step_and_gray_sums_its_waits():
    v = _view(steps=[_step(apply=0.010, gray=0.1, device_wait=0.3),
                     _step(apply=0.030, gray=0.2, device_wait=0.2)])
    assert measures.load_reader("apply_ms.steady")(v) == pytest.approx(20.0)
    assert measures.load_reader("gray_ms.catchup")(v) == pytest.approx(400.0)
    # untraced runs carry no stage spans: the metric is left out
    assert measures.load_reader("apply_ms.steady")(
        _view(steps=[_step()])) is None


def test_idle_share_and_kernel_time_from_the_trace():
    trace = {"busy_s": 2.5, "window_s": 10.0, "steps": 3, "n_devices": 1,
             "kernels": {"ell_vertex_sums": {"seconds": 1.0, "launches": 9},
                         "ell_vertex_maxima": {"seconds": 0.5,
                                               "launches": 3}}}
    v = _view(trace=trace, steps=[_step()])
    assert measures.load_reader("device_idle_share.steady")(v) == \
        pytest.approx(75.0)
    assert measures.load_reader("ell_kernel_ms.catchup")(v) == \
        pytest.approx(500.0)
    assert measures.load_reader("device_idle_share.steady")(_view()) is None
