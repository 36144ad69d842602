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

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


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


def test_every_cell_resolves():
    cells = resolve(ROOT)
    assert list(cells) == ["transactions-steady", "mathoverflow-catchup"]
    for cfg, tr, metrics in cells.values():
        gen.twin_from_config(cfg)
        assert "setup_s" in metrics
        for m in metrics:
            assert callable(measures.load_reader(m))


def test_contract_shape():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
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
    for w in b["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
        per = [m for m in b["per_layer"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert per and all(w["name"] in
                           next(e for e in b["end_to_end"]
                                if e["name"] == m["moves"]).get(
                               "workloads", [w["name"]]) for m in per)
    for c in b["configs"]:
        assert c["file"].startswith("bench/configs/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg["reduced"])


def test_a_new_cell_needs_only_new_files(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    # new configuration, traffic mix, metric reader: new files only
    cfg = json.loads((root / "bench/configs/igpm-transactions.json")
                     .read_text())
    cfg["name"] = "igpm-friends2008"
    cfg["dataset"] = {"name": "friends2008", "kind": "scale_free",
                      "n_vertices": 224879, "n_edges": 3871909,
                      "n_steps": 6893}
    (root / "bench/configs/igpm-friends2008.json").write_text(
        json.dumps(cfg))
    tr = json.loads((root / "bench/traffic/steady-transactions.json")
                    .read_text())
    tr["rate_eps"] = 123
    (root / "bench/traffic/steady-friends2008.json").write_text(
        json.dumps(tr))
    (root / "bench/metrics/steps_per_s.steady.py").write_text(
        "def read(view):\n    return len(view.steps) / view.seconds\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "igpm-friends2008",
                         "source": "https://arxiv.org/abs/1812.10321",
                         "file": "bench/configs/igpm-friends2008.json",
                         "reduced": ["n_steps"], "why": "scale-free"})
    b["workloads"].append({"name": "friends2008-steady",
                           "config": "igpm-friends2008",
                           "traffic": "steady-friends2008", "chips": 1,
                           "why": "the paper's million-scale graph"})
    for m in b["end_to_end"]:
        if m["name"] in ("delta_latency_p50_ms", "delta_latency_p95_ms"):
            m["workloads"].append("friends2008-steady")
    b["per_layer"].append({"name": "steps_per_s.steady", "unit": "steps/s",
                           "better": "higher", "source": "host_clock",
                           "layer": "ingress",
                           "moves": "delta_latency_p50_ms",
                           "workloads": ["friends2008-steady"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cells = resolve(root)
    cfg2, tr2, metrics = cells["friends2008-steady"]
    assert tr2.rate_eps == 123 and "steps_per_s.steady" in metrics
    assert gen.twin_from_config(cfg2).n_vertices == 224879
    after = {p: p.read_bytes() for p in before}
    assert after == before
