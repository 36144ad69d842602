"""BENCHMARK.json resolves every file by name, keeps to the contract's
shape, and takes a new cell, configuration, traffic mix or metric as new
files plus new entries, with no edit to a file already there."""

import json
import re
import shutil
from pathlib import Path

import pytest

from bench import generator as gen
from bench import measures
from bench.tests.test_generator import check_stream

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# cells the benchmark has taken: others may be added, none may go
ACCEPTED = ("transactions-steady", "mathoverflow-catchup")


def resolve(root: Path) -> dict:
    """Every cell's config, traffic and metric files, found by name."""
    b = json.loads((root / "BENCHMARK.json").read_text())
    cfgs = {c["name"]: c for c in b["configs"]}
    out = {}
    for w in b["workloads"]:
        cfg = json.loads((root / cfgs[w["config"]]["file"]).read_text())
        tr = gen.traffic_from_file(w["traffic"], root / "bench" / "traffic")
        metrics = [m["name"] for m in b["end_to_end"] + b["per_layer"]
                   if w["name"] in m.get("workloads", [w["name"]])]
        for m in metrics:
            assert (root / "bench" / "metrics" / f"{m}.py").is_file(), m
        out[w["name"]] = (cfg, tr, metrics)
    return out


def check_cells(root: Path) -> dict:
    """Every cell resolves: its twin, its traffic, every metric's reader."""
    cells = resolve(root)
    for cfg, tr, metrics in cells.values():
        gen.twin_from_config(cfg)
        assert "setup_s" in metrics
        for m in metrics:
            assert callable(measures.load_reader(
                m, root / "bench" / "metrics"))
    return cells


def check_contract(root: Path) -> None:
    """BENCHMARK.json keeps to the contract's shape."""
    b = json.loads((root / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and 1 <= b["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    # a cell takes one chip, or four where what it measures exists only
    # across chips; at most half of the cells (one always) take four
    cells = b["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    for w in cells:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        per = [m for m in b["per_layer"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert per and all(w["name"] in
                           next(e for e in b["end_to_end"]
                                if e["name"] == m["moves"]).get(
                               "workloads", [w["name"]]) for m in per)
    for c in b["configs"]:
        assert c["file"].startswith("bench/configs/")
        cfg = json.loads((root / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg["reduced"])


def test_every_cell_resolves():
    cells = check_cells(ROOT)
    assert set(ACCEPTED) <= set(cells)


def test_contract_shape():
    check_contract(ROOT)


def _copy_json(root: Path, src: str, dst: str, **changes) -> None:
    raw = json.loads((root / src).read_text())
    raw.update(changes)
    (root / dst).write_text(json.dumps(raw))


def test_a_new_cell_needs_only_new_files(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    # new configuration, traffic mixes, metric reader: new files only,
    # under names no real entry takes
    _copy_json(root, "bench/configs/igpm-transactions.json",
               "bench/configs/probe-friends2008.json",
               name="probe-friends2008",
               dataset={"name": "friends2008", "kind": "scale_free",
                        "n_vertices": 224879, "n_edges": 3871909,
                        "n_steps": 6893})
    _copy_json(root, "bench/traffic/steady-transactions.json",
               "bench/traffic/probe-steady.json", rate_eps=123)
    _copy_json(root, "bench/traffic/catchup-backlog.json",
               "bench/traffic/probe-backlog.json")
    (root / "bench/metrics/probe_steps_per_s.steady.py").write_text(
        "def read(view):\n    return len(view.steps) / view.seconds\n")
    # new entries: a configuration, a one-chip and a four-chip cell, the
    # cells' names appended to the metrics they report, one new metric
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "probe-friends2008",
                         "source": "https://arxiv.org/abs/1812.10321",
                         "file": "bench/configs/probe-friends2008.json",
                         "reduced": ["n_steps"], "why": "scale-free"})
    b["workloads"] += [
        {"name": "probe-steady", "config": "probe-friends2008",
         "traffic": "probe-steady", "chips": 1,
         "why": "the paper's million-scale graph"},
        {"name": "probe-sharded", "config": "probe-friends2008",
         "traffic": "probe-backlog", "chips": 4,
         "why": "the graph sharded over four chips"}]
    for m in b["end_to_end"]:
        if m["name"] in ("delta_latency_p50_ms", "delta_latency_p95_ms"):
            m["workloads"].append("probe-steady")
        if m["name"] == "events_per_s":
            m["workloads"].append("probe-sharded")
    for m in b["per_layer"]:
        if m["name"].endswith(".catchup"):
            m["workloads"].append("probe-sharded")
    b["per_layer"].append({"name": "probe_steps_per_s.steady",
                           "unit": "steps/s",
                           "better": "higher", "source": "host_clock",
                           "layer": "ingress",
                           "moves": "delta_latency_p50_ms",
                           "workloads": ["probe-steady"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    # every check that enumerates cells passes on the extended copy
    cells = check_cells(root)
    assert set(ACCEPTED) <= set(cells)
    check_contract(root)
    for name in cells:
        check_stream(root, name)
    cfg2, tr2, metrics = cells["probe-steady"]
    assert tr2.rate_eps == 123 and "probe_steps_per_s.steady" in metrics
    assert gen.twin_from_config(cfg2).n_vertices == 224879
    assert "events_per_s" in cells["probe-sharded"][2]
    after = {p: p.read_bytes() for p in before}
    assert after == before
