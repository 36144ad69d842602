"""A whole run at test size on the CPU, with the chip check skipped: the
sound program is correct, and every fault the cells can have, and the
bfloat16 control, come out not correct."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"

DRIVER = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
from pathlib import Path
from bench import faults, generator as gen, harness
variant, traffic = sys.argv[1], sys.argv[2]
cfg = json.loads(Path({data!r}, "tiny-config.json").read_text())
tr = gen.traffic_from_file(traffic, Path({data!r}))
breaker = None
if variant == "bf16_rwr":
    faults.bf16_rwr()
elif variant != "sound":
    breaker = faults.FAULTS[variant]
res = harness.run_cell({{"name": "tiny"}}, cfg, tr, 20261016, 1.0, False,
                       time.monotonic(), breaker=breaker)
print(json.dumps({{"correct": res["correct"],
                  "compared": res["compared"]}}))
"""


def run(variant: str, traffic: str = "tiny-steady") -> dict:
    code = DRIVER.format(root=str(ROOT), src=str(ROOT / "src"),
                         data=str(DATA))
    p = subprocess.run([sys.executable, "-c", code, variant, traffic],
                       capture_output=True, text=True, timeout=600,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                            "HOME": str(ROOT)})
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("traffic", ["tiny-steady", "tiny-backlog"])
def test_sound_run_is_correct(traffic):
    out = run("sound", traffic)
    assert out["correct"], out["compared"]


@pytest.mark.parametrize("variant", ["unchanged_state", "half_batch",
                                     "altered_answer", "dropped_results",
                                     "shrunk_pem", "halved_threshold",
                                     "bf16_rwr"])
def test_broken_run_is_not_correct(variant):
    out = run(variant)
    assert not out["correct"], out["compared"]
