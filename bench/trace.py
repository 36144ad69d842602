"""Profiler capture around the window, and its reduction to numbers.

``start`` starts ``jax.profiler`` and puts an anchor at one instant on three
clocks: the host's monotonic clock (which times the window), the program's
span clock (``obs`` spans, ``perf_counter`` microseconds since the
tracer's epoch) and the profiler's own (a ``TraceAnnotation``). ``stop``
stops it. ``extract`` uses the anchor to put the window and the engine's
stage spans on the profiler's clock and keeps the device operations;
``reduce`` turns that into busy time, idle share, per-kernel launches and
device time, and the breakdown. ``reduce`` works on plain lists, so it is
checked on a small recorded trace (``bench/tests/data/trace_small.json``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import time
from typing import Dict, List, Optional, Tuple

ANCHOR = "bench/anchor"

# HLO instruction names of the served path's Pallas kernels: a launch is a
# device op whose instruction is named after the kernel (the custom call
# itself, or the fusion XLA folds it into), never an op that only takes a
# kernel's output as an operand
KERNELS = ("ell_vertex_sums", "ell_vertex_maxima")

_INSTR = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*\s*=\s*(.*)$", re.S)
_ARRAY = re.compile(r"^[a-z]+\d*\[([\d,]*)\]")


def start(trace_dir: str, obs) -> dict:
    import jax

    jax.profiler.start_trace(trace_dir)
    anchor = {"monotonic": time.monotonic(),
              "perf_counter": time.perf_counter()}
    obs.instant(ANCHOR)
    with jax.profiler.TraceAnnotation(ANCHOR):
        pass
    return anchor


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def remove(trace_dir: str) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)


def _span_epoch(obs, anchor: dict) -> Optional[float]:
    """perf_counter value of the span clock's zero."""
    for ev in obs.tracer.events():
        if ev.get("name") == ANCHOR:
            return anchor["perf_counter"] - ev["ts"] / 1e6
    return None


def extract(trace_dir: str, anchor: dict, obs, t0: float,
            t_end: float) -> dict:
    """Device ops, host stage spans and the window, in profiler ns, and
    the anchor as (profiler ns, host monotonic s)."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    anchor_ns = None
    devices: Dict[str, List[list]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR and anchor_ns is None:
                        anchor_ns = ev.start_ns
        elif plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    ops.append([ev.name, ev.start_ns, ev.duration_ns])
            devices[plane.name] = ops
    if anchor_ns is None:
        raise RuntimeError("the trace holds no anchor annotation")

    def mono_ns(t: float) -> float:
        return anchor_ns + (t - anchor["monotonic"]) * 1e9

    spans = []
    epoch = _span_epoch(obs, anchor)
    if epoch is not None:
        for ev in obs.tracer.events():
            if ev.get("ph") != "X":
                continue
            pc = epoch + ev["ts"] / 1e6
            spans.append([ev["name"],
                          anchor_ns + (pc - anchor["perf_counter"]) * 1e9,
                          ev["dur"] * 1e3])
    return {"window": [mono_ns(t0), mono_ns(t_end)], "devices": devices,
            "spans": spans, "clock": [anchor_ns, anchor["monotonic"]]}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def short_name(op: str) -> str:
    """``%ell_vertex_sums.8 = f32[512,128]{1,0:T(8,128)} custom-call(...)``
    → ``ell_vertex_sums f32[512,128] custom-call``."""
    m = _INSTR.match(op)
    if not m:
        return op[:100]
    name, rest = m.group(1), m.group(2).lstrip()
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = "tuple", rest[i + 1:]
    else:
        shape, _, rest = rest.partition(" ")
        shape = shape.split("{")[0]
    kind = rest.split("(")[0].strip().split(" ")[-1] if rest else ""
    return f"{name} {shape} {kind}".strip()[:100]


def kernel_of(op: str) -> Optional[str]:
    """The kernel an op launches, by its instruction name."""
    m = _INSTR.match(op)
    return m.group(1) if m and m.group(1) in KERNELS else None


def out_rows(op: str) -> Optional[int]:
    """Rows of the op's (…, rows, lanes) array result."""
    m = _INSTR.match(op)
    a = _ARRAY.match(m.group(2).lstrip()) if m else None
    dims = [int(x) for x in a.group(1).split(",") if x] if a else []
    return dims[-2] if len(dims) >= 2 else None


class _Stages:
    """Host stage spans (engine/executor/ingress), queried by time."""

    def __init__(self, spans: List[list]):
        keep = sorted((s, s + d, n) for n, s, d in spans
                      if n.split("/", 1)[0] in ("engine", "executor",
                                                "ingress"))
        self._starts = [s for s, _, _ in keep]
        self._spans = keep
        self._max = max((e - s for s, e, _ in keep), default=0.0)

    def at(self, t: float) -> str:
        """The innermost span covering ``t``."""
        i = bisect.bisect_right(self._starts, t)
        best = None
        while i > 0:
            i -= 1
            s, e, n = self._spans[i]
            if s < t - self._max:
                break
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (n, e - s)
        return best[0] if best else "no span"


def _leaves(ops: List[list]) -> List[bool]:
    """Which ops contain no other op (a while loop or a conditional holds
    the ops of its body; only the innermost ones do the work)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    leaf = [True] * len(ops)
    stack: List[int] = []
    for i in order:
        s = ops[i][1]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            leaf[stack[-1]] = False
        stack.append(i)
    return leaf


# idle gaps shorter than this are summed under one name, not attributed
SHORT_GAP_NS = 1e5


def reduce(ex: dict, top: int = 10) -> dict:
    """Busy and window seconds (mean over the devices traced), each
    kernel's launches (start ns, device ns, result rows) and device time,
    the leaf device ops that took most time, and idle time by what the
    host was doing."""
    w0, w1 = ex["window"]
    busy_total = 0.0
    per_op: Dict[str, float] = {}
    launches: Dict[str, List[list]] = {k: [] for k in KERNELS}
    idle: Dict[str, float] = {}
    devices = ex["devices"]
    stages = _Stages(ex["spans"])
    for ops in devices.values():
        iv = []
        inside = [op for op in ops
                  if min(op[1] + op[2], w1) > max(op[1], w0)]
        for (name, start, dur), leaf in zip(inside, _leaves(inside)):
            a, b = max(start, w0), min(start + dur, w1)
            iv.append((a, b))
            if not leaf:
                continue
            key = short_name(name)
            per_op[key] = per_op.get(key, 0.0) + (b - a) / 1e9
            k = kernel_of(name)
            if k is not None:
                launches[k].append([start, dur, out_rows(name)])
        merged = _union(iv)
        busy_total += sum(b - a for a, b in merged) / 1e9
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                stage = (stages.at((a + b) / 2) if b - a >= SHORT_GAP_NS
                         else "gaps under 0.1 ms")
                idle[stage] = idle.get(stage, 0.0) + (b - a) / 1e9
    n_dev = max(len(devices), 1)
    window_s = (w1 - w0) / 1e9
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_total / n_dev, "window_s": window_s,
            "kernels": {k: {"seconds": sum(d for _, d, _ in v) / 1e9 / n_dev,
                            "launches": len(v)}
                        for k, v in launches.items()},
            "launches": launches,
            "device_ops": [[n, s / n_dev] for n, s in ranked],
            "idle_gaps": [[n, s / n_dev] for n, s in gaps],
            "n_devices": len(devices), "clock": ex.get("clock")}


def reduce_dir(trace_dir: str, anchor: dict, obs, t0: float,
               t_end: float) -> dict:
    return reduce(extract(trace_dir, anchor, obs, t0, t_end))
