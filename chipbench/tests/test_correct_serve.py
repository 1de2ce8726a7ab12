"""The serve cells' check on whole runs at a small size: sound runs pass,
the float8 control and an altered token fail."""
import pytest

from chipbench import faults
from chipbench.tests.tiny import execute

CELLS = ["serve.smollm-360m.chat", "serve.h2o-danube3-4b.batch"]
SEED = 2 ** 31 + 99


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_the_control_fails(cell):
    out, rec = execute(cell, SEED, modes=("f32", "fp8"))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    lim = out["checks"]["served_logit_gap"]["limit"]
    assert rec["control"]["fp8"]["served_logit_gap"] > lim, rec["control"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token_fails(cell):
    with faults.altered_token():
        out, _ = execute(cell, SEED)
    assert not out["correct"], out["checks"]
