"""A train mix with a ``mesh`` key runs the whole cell on that mesh: here
a 2x2 ("data", "model") mesh of four host devices, at a size the CPU
holds, with the check deciding ``correct`` as on one chip."""
import json
import os
import subprocess
import sys

from chipbench import run as R

SCRIPT = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from chipbench.tests import tiny
from chipbench import run as R
cell = "train.smollm-360m.s4096"
over = dict(tiny.OVERRIDES[cell])
over["traffic"] = dict(over["traffic"], mesh={{"data": 2, "model": 2}})
over["cell"] = {{"chips": 4}}
out, rec = R.execute(cell, 2 ** 31 + 77, 0.5, False, require_chip=False,
                     overrides=over, peaks=tiny.PEAKS)
print(json.dumps({{"correct": out["correct"], "checks": out["checks"],
                  "count": out["device"]["count"],
                  "mesh": rec["readings"]["mesh"], "steps": rec["steps"],
                  "holding": rec["readings"]["devices_holding_params"]}}))
"""


def test_train_cell_runs_on_a_two_by_two_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(root=R.ROOT, src=os.path.join(R.ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["count"] == 4 and got["mesh"] == {"data": 2, "model": 2}
    assert got["steps"] >= 1 and got["holding"] == 4
    assert got["correct"], got["checks"]
