"""The train cell's check on a whole run at a small size: sound runs pass,
the float8 control and each fault the cell can have fail."""
import pytest

from chipbench import faults
from chipbench.tests.tiny import execute

CELL = "train.smollm-360m.s4096"
SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def sound():
    return execute(CELL, SEED, modes=("f32", "fp8"))


def test_sound_run_is_correct_and_reports_its_metrics(sound):
    out, rec = sound
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"train_tok_s", "setup_s"}
    assert rec["steps"] >= 1 and rec["tokens"] == rec["steps"] * 4 * 64


def test_float8_control_fails(sound):
    out, rec = sound
    control = rec["control"]["fp8"]
    assert any(control[k] > c["limit"] for k, c in out["checks"].items()), \
        (control, out["checks"])


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_fault_under_the_timed_path_fails(fault):
    with faults.FAULTS[fault]():
        out, _ = execute(CELL, SEED)
    assert not out["correct"], out["checks"]
