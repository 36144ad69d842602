"""Shared arithmetic of the metric readers in ``bench/metrics/``.

Every reader is a file ``bench/metrics/<metric name>.py`` with one function
``read(view) -> float | None`` over the run's :class:`bench.harness.RunView`.
A reader that finds nothing to read returns None and the metric is left
out of the result line.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Callable, Dict, Optional

import numpy as np

METRICS_DIR = Path(__file__).resolve().parent / "metrics"


def load_reader(name: str, directory: Path = METRICS_DIR) -> Callable:
    """The ``read`` function of ``<directory>/<name>.py``."""
    path = directory / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def percentile_ms(samples_s: np.ndarray, q: float) -> Optional[float]:
    """The q-th percentile of a population in ms (numpy's linear rule)."""
    if len(samples_s) == 0:
        return None
    return 1e3 * float(np.percentile(samples_s, q))


def stage_ms(view, *stages: str) -> Optional[float]:
    """Mean per window step of the summed ``engine/*`` stage spans, ms.
    Present only in a traced run (the spans are off otherwise)."""
    per_step = [sum(s.stats.stage_s.get(k, 0.0) for k in stages)
                for s in view.steps if s.stats.stage_s]
    if not per_step:
        return None
    return 1e3 * float(np.mean(per_step))


def kernel_ms(view) -> Optional[float]:
    """Device time of the ELL kernels per step of the traced window, ms."""
    if view.trace is None or not view.trace.get("steps"):
        return None
    total = sum(k["seconds"] for k in view.trace["kernels"].values())
    if total <= 0:
        return None
    return 1e3 * total / view.trace["steps"]


def roofline_percent(view, kernel: str) -> Optional[float]:
    if view.roofline is None or view.roofline.get(kernel) is None:
        return None
    return view.roofline[kernel].percent


def idle_percent(view) -> Optional[float]:
    if (view.trace is None or not view.trace["n_devices"]
            or view.trace["window_s"] <= 0):
        return None
    return 100.0 * (1.0 - view.trace["busy_s"] / view.trace["window_s"])


def rate(view) -> Optional[float]:
    """Events of the window's whole steps over the time they took."""
    if view.rate_s <= 0:
        return None
    return view.rate_events / view.rate_s


def read_all(names, view) -> Dict[str, float]:
    out = {}
    for name in names:
        v = load_reader(name)(view)
        if v is not None:
            out[name] = float(v)
    return out
