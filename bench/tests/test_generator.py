"""The benchmark's stream and arrival generator."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import generator as gen

ROOT = Path(__file__).resolve().parents[2]


def _cells():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfgs = {c["name"]: json.loads((ROOT / c["file"]).read_text())
            for c in b["configs"]}
    return [(w["name"], cfgs[w["config"]], gen.traffic_from_file(w["traffic"]),
             b["run_seconds"]) for w in b["workloads"]]


def test_poisson_count_is_fixed_and_order_varies():
    a = gen.poisson_arrivals(300.0, 30.0, 0.01, gen.seed_rng(1, 1))
    b = gen.poisson_arrivals(300.0, 30.0, 0.01, gen.seed_rng(2, 1))
    assert a.n_events == b.n_events == 9000
    assert len(a.times) == 3000 and a.times[1] == pytest.approx(0.01)
    assert not np.array_equal(a.counts, b.counts)


def test_same_seed_same_inputs_large_seed():
    seed = 2 ** 31 + 12345
    twin = gen.Twin("t", "sparse_dense", 4096, 40000, 100)
    s1 = gen.make_stream(twin, 0.5, 500, seed)
    s2 = gen.make_stream(twin, 0.5, 500, seed)
    for x, y in zip(s1, s2):
        np.testing.assert_array_equal(x, y)
    s3 = gen.make_stream(twin, 0.5, 500, seed + 1)
    assert not np.array_equal(s1.tail_src, s3.tail_src)


def test_events_are_the_next_edges_grouped_by_region():
    twin = gen.Twin("t", "sparse_dense", 4096, 40000, 100)
    src, dst = gen.gen_edges(twin, gen.seed_rng(5, 0))
    s = gen.make_stream(twin, 0.5, 500, 5)
    warm = len(s.start_src)
    region = np.maximum(s.tail_src, s.tail_dst) // (4096 // 64)
    assert np.all(np.diff(region) >= 0)
    drawn = sorted(zip(src[warm:warm + 500].tolist(),
                       dst[warm:warm + 500].tolist()))
    assert sorted(zip(s.tail_src.tolist(), s.tail_dst.tolist())) == drawn


def test_backlog_window_covers_its_budget():
    tr = gen.Traffic("b", "backlog", 0.5, 0.01, "lockstep", 256, 4096,
                     warmup_ticks=3, events_per_tick=256, budget_eps=1000)
    warm, win, tail = gen.phase_arrivals(tr, 30.0, 1)
    assert warm.n_events == 3 * 256
    assert win.n_events >= 1000 * 30
    assert tail.n_events == 0


def test_open_loop_phases_follow_one_another_on_one_clock():
    tr = gen.Traffic("s", "open_poisson", 0.5, 0.01, "shed", 256, 4096,
                     rate_eps=200.0, warmup=((0.5, 2.0), (1.0, 3.0)),
                     tail_s=1.5)
    warm, win, tail = gen.phase_arrivals(tr, 10.0, 7)
    assert gen.warmup_seconds(tr) == 5.0
    # 0.5·200·2 + 200·3 events before the window, at ticks in [0, 5)
    assert warm.n_events == 200 + 600
    assert warm.times[0] == 0.0 and warm.times[-1] < 5.0
    assert win.n_events == 2000 and win.times[0] == pytest.approx(5.0)
    assert tail.n_events == 300 and tail.times[0] == pytest.approx(15.0)
    assert np.all(np.diff(gen.concat([warm, win, tail]).times) > 0)


def test_tail_is_sized_from_edges_per_step_and_raises_when_short():
    # sx-mathoverflow carries 215 edges per timestep, under the 256 the
    # program's build_workload assumed; the tail must still be full
    twin = gen.Twin("sx-mathoverflow", "dense", 24818, 506550, 2350)
    assert twin.edges_per_step == 215
    s = gen.make_stream(twin, 0.5, 30000, 3)
    assert len(s.tail_src) == 30000
    with pytest.raises(ValueError):
        gen.make_stream(twin, 0.99, 30000, 3)


@pytest.mark.parametrize("cell", [c[0] for c in _cells()])
def test_each_cell_stream_covers_warmup_and_window(cell):
    name, cfg, tr, seconds = next(c for c in _cells() if c[0] == cell)
    twin = gen.twin_from_config(cfg)
    stream, warm, win, tail = gen.build_inputs(twin, tr, seconds, 99)
    assert len(stream.tail_src) == (warm.n_events + win.n_events
                                    + tail.n_events)
    if tr.kind == "open_poisson":
        assert win.n_events == round(tr.rate_eps * seconds)
        assert tail.n_events == round(tr.rate_eps * tr.tail_s)
    else:
        assert win.n_events >= tr.budget_eps * seconds
    # the start graph holds the configured share of the stream
    assert len(stream.start_src) == pytest.approx(
        tr.warmup_frac * twin.n_edges, rel=0.02)  # self-loops dropped
