"""Work counts and peaks, on hand-worked shapes."""

import pytest

from bench import roofline


def test_vertex_sums_work_by_hand():
    # 1,000 live arcs, 100 vertices, 4 columns:
    # flops 2·1000·4 = 8,000; bytes 1000·(4+4) + 2·100·4·4 = 11,200
    w = roofline.vertex_sums_work(1000, 100, 4)
    assert w.flops == 8000 and w.bytes == 11200


def test_vertex_maxima_work_by_hand():
    # no weights: bytes 1000·4 + 2·100·4·4 = 7,200; one compare per arc·col
    w = roofline.vertex_maxima_work(1000, 100, 4)
    assert w.flops == 4000 and w.bytes == 7200


def test_share_takes_the_larger_bound_and_names_it():
    # 819 MB at 819 GB/s = 1 ms; 197 MFLOP at 197 TFLOP/s = 1 µs
    w = roofline.Work(flops=197e6, bytes=819e6)
    s = roofline.share(w, 0.004, "TPU v5 lite")
    assert s.bound == "bytes" and s.percent == pytest.approx(25.0)
    s = roofline.share(roofline.Work(flops=197e12, bytes=1.0), 2.0,
                       "TPU v5 lite")
    assert s.bound == "flops" and s.percent == pytest.approx(50.0)


def test_total_work_sums_launches():
    w = roofline.total_work("ell_vertex_sums", [(1000, 100, 4),
                                                (1000, 100, 4)])
    assert w.flops == 16000 and w.bytes == 22400


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
    assert roofline.share(roofline.Work(1.0, 1.0), 0.0, "TPU v5 lite") is None


def test_columns_read_from_the_packed_result():
    # 512 vertices: 4 columns pack 32 vertices to a row → 16 rows;
    # 128 columns one to a row → 512 rows; 64 vertices at 4 columns need
    # 2 rows, padded to 8, which a width of 16 also gives: the narrowest
    assert roofline.columns_of(16, 512) == 4
    assert roofline.columns_of(512, 512) == 128
    assert roofline.columns_of(8, 64) == 1
    assert roofline.columns_of(None, 512) == 128


def test_kernel_shares_charge_each_launch_its_steps_graph():
    ms = 1e6
    steps = [(0.0, 10 * ms, 512, 400, 100_000),
             (20 * ms, 30 * ms, 2048, 1500, 4_000)]
    launches = {"ell_vertex_sums": [[1 * ms, 0.5 * ms, 16],     # step 0, 4 col
                                    [21 * ms, 0.5 * ms, 2048],  # step 1, 128
                                    [15 * ms, 0.5 * ms, 16]],   # no step
                "ell_vertex_maxima": []}
    out = roofline.kernel_shares(launches, steps, "TPU v5 lite")
    w = roofline.total_work("ell_vertex_sums", [(100_000, 400, 4),
                                                (4_000, 1500, 128)])
    want = roofline.share(w, 1e-3, "TPU v5 lite")
    assert out["ell_vertex_sums"].percent == pytest.approx(want.percent)
    assert out["ell_vertex_sums"].bound == want.bound == "bytes"
    assert "ell_vertex_maxima" not in out
