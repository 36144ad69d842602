"""The trace reduction, on a hand-made trace and on a small one recorded
from a served window on one v5e."""

import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"

# op names as a v5e trace gives them (layouts shortened)
WHILE = ("%while.3 = (s32[]{:T(128)}, f32[512]{0}) while((s32[], f32[512]) "
         "%tuple.4), condition=%region_5, body=%region_0")
SUMS = ("%ell_vertex_sums.8 = f32[512,128]{1,0:T(8,128)S(1)} custom-call("
        "s32[16896,66]{1,0} %pad_add_fusion.5, f32[16896,64]{1,0} %b)")
USES_SUMS = ("%slice_multiply_fusion = f32[512,80]{1,0} fusion(f32[512,128]"
             "{1,0} %ell_vertex_sums.8), kind=kLoop")
FUSED = ("%ell_vertex_sums.6 = f32[2,512,128]{2,1,0} fusion(f32[2,512,128] "
         "%get-tuple-element.1754, s32[] %x), kind=kCustom")


def test_reduction_by_hand():
    ms = 1e6  # ns
    ex = {"window": [0.0, 10 * ms],
          "devices": {"/device:TPU:0": [
              [WHILE, 1 * ms, 3 * ms],                    # holds the next two
              [SUMS, 1.5 * ms, 1 * ms],
              [USES_SUMS, 2.5 * ms, 0.5 * ms],
              ["%ell_vertex_maxima = f32[16,128]{1,0} custom-call(s32[8,66] "
               "%a, f32[16,128] %b)", 6 * ms, 1 * ms],
              ["%copy.2 = f32[8] copy(f32[8] %c)", 9.5 * ms, 2 * ms]]},
          # the last op is clipped at the window's end (10 ms)
          "spans": [["engine/extract", 4 * ms, 2 * ms],
                    ["executor/step", 0.0, 10 * ms],
                    ["other/thing", 7 * ms, 2 * ms]]}
    out = trace.reduce(ex)
    # busy: [1,4] ∪ [6,7] ∪ [9.5,10] = 4.5 ms of a 10 ms window
    assert out["busy_s"] == pytest.approx(4.5e-3)
    assert out["window_s"] == pytest.approx(10e-3)
    # one launch each: the fusion that only reads a kernel's result is not
    # one, and the while loop around them is no leaf
    assert out["kernels"]["ell_vertex_sums"] == {"seconds": pytest.approx(
        1e-3), "launches": 1}
    assert out["launches"]["ell_vertex_sums"] == [[1.5 * ms, 1 * ms, 512]]
    assert out["kernels"]["ell_vertex_maxima"]["seconds"] == \
        pytest.approx(1e-3)
    gaps = dict(out["idle_gaps"])
    # [0,1] and [7,9.5] lie in executor/step only; [4,6] in engine/extract
    assert gaps["engine/extract"] == pytest.approx(2e-3)
    assert gaps["executor/step"] == pytest.approx(3.5e-3)
    ops = dict(out["device_ops"])
    assert "while tuple while" not in ops
    assert ops["ell_vertex_sums f32[512,128] custom-call"] == \
        pytest.approx(1e-3)
    assert ops["copy f32[8] copy"] == pytest.approx(0.5e-3)


def test_short_names():
    assert trace.short_name(SUMS) == "ell_vertex_sums f32[512,128] custom-call"
    assert trace.short_name(WHILE) == "while tuple while"
    assert trace.kernel_of(USES_SUMS) is None
    assert trace.kernel_of(SUMS) == "ell_vertex_sums"
    assert trace.out_rows(FUSED) == 512


def test_recorded_trace():
    path = DATA / "trace_small.json"
    if not path.is_file():
        pytest.skip("no recorded trace")
    ex = json.loads(path.read_text())
    expect = ex.pop("expect")
    out = trace.reduce(ex)
    assert out["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    for k, v in expect["kernels"].items():
        assert out["kernels"][k]["launches"] == v["launches"]
        assert out["kernels"][k]["seconds"] == pytest.approx(v["seconds"])
    assert 0.0 < out["busy_s"] <= out["window_s"]
