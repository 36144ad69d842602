"""Work counts of the ELL kernels and the chip peaks they are read against.

A kernel's roofline share is the least time the chip could take for the
work the algorithm needs, divided by the kernel's measured device time.
The least time is the larger of bytes ÷ peak bandwidth and FLOPs ÷ peak
compute; ``bound`` says which. The work is counted from the graph each
launch swept, live arcs and vertices and live columns, never from padded
ELL rows or slots, so a kernel that skips padding, or a COO replacement,
is read against the same work.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

# Published per-chip peaks, keyed by jax's device_kind. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9},
}

ID_BYTES = 4       # int32 column id
VAL_BYTES = 4      # float32 arc weight / vertex value

# the kernels' lane-packed operand layout (kernels/spmv_ell/spmv_ell.py):
# X and Y are (rows, 128) f32 blocks, 128/dp vertices to a row, rows a
# multiple of 8
LANES = 128
SUBLANES = 8


class Work(NamedTuple):
    flops: float
    bytes: float


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add its "
                       "published numbers to bench/roofline.py")
    return PEAKS[device_kind]


def vertex_sums_work(arcs: int, vertices: int, columns: int) -> Work:
    """y[v] = Σ_{u→v} w·x[u] over ``columns`` columns: every live arc's
    column id and weight read once, X read once, Y written once; one
    multiply and one add per arc and column."""
    return Work(flops=2.0 * arcs * columns,
                bytes=arcs * (ID_BYTES + VAL_BYTES)
                + 2.0 * vertices * columns * VAL_BYTES)


def vertex_maxima_work(arcs: int, vertices: int, columns: int) -> Work:
    """y[v] = max_{u→v} x[u]: column ids only (no weights), X read once,
    Y written once; one comparison per arc and column."""
    return Work(flops=1.0 * arcs * columns,
                bytes=arcs * ID_BYTES + 2.0 * vertices * columns * VAL_BYTES)


WORK = {"ell_vertex_sums": vertex_sums_work,
        "ell_vertex_maxima": vertex_maxima_work}


class Share(NamedTuple):
    percent: float
    bound: str        # "bytes" or "flops"


def share(work: Work, seconds: float, device_kind: str) -> Optional[Share]:
    """Least time over measured time, in percent; None without a time."""
    if seconds <= 0:
        return None
    pk = peaks(device_kind)
    t_bytes = work.bytes / pk["bytes_per_s"]
    t_flops = work.flops / pk["flops"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return Share(100.0 * max(t_bytes, t_flops) / seconds, bound)


def total_work(kernel: str, launches) -> Work:
    """Sum over launches given as (arcs, vertices, columns)."""
    fn = WORK[kernel]
    flops = byts = 0.0
    for arcs, vertices, columns in launches:
        w = fn(arcs, vertices, columns)
        flops += w.flops
        byts += w.bytes
    return Work(flops, byts)


def packed_rows(n: int, dp: int) -> int:
    per_row = LANES // dp
    return -(-(-(-n // per_row)) // SUBLANES) * SUBLANES


def columns_of(rows: Optional[int], n_tile: int) -> int:
    """Columns one launch carried, read from its packed result's rows: the
    narrowest power-of-two width whose packing of ``n_tile`` vertices has
    that many rows. It is the packed width, the power of two at or above
    the logical column count, so X and Y bytes are counted high by less
    than 2x; a result the layout cannot explain counts as 128 columns."""
    dp = 1
    while dp <= LANES:
        if rows is not None and packed_rows(n_tile, dp) == rows:
            return dp
        dp *= 2
    return LANES


def kernel_shares(launches: Dict[str, List[Sequence[float]]],
                  steps: List[Tuple[float, float, int, int, int]],
                  device_kind: str) -> Dict[str, Share]:
    """Each kernel's share of its roofline over the launches of a traced
    window. ``launches[kernel]`` holds (start ns, device ns, result rows)
    per launch; ``steps`` holds (start ns, end ns, tile vertices, live
    vertices, live arcs) per served step, in order. A launch is charged
    the live graph of the step it ran in; a launch outside every step is
    left out, its time with its work."""
    starts = [s[0] for s in steps]
    out = {}
    for kernel, ls in launches.items():
        work = []
        seconds = 0.0
        for t, dur, rows in ls:
            i = bisect.bisect_right(starts, t) - 1
            if i < 0 or t > steps[i][1]:
                continue
            _, _, n_tile, vertices, arcs = steps[i]
            work.append((arcs, vertices, columns_of(rows, n_tile)))
            seconds += dur / 1e9
        sh = share(total_work(kernel, work), seconds, device_kind)
        if sh is not None:
            out[kernel] = sh
    return out
