"""Every cell resolves, by name alone, to the files that run it."""
import json
import os

import pytest

from chipbench import run as R

ROOT = R.ROOT


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS = [w["name"] for w in _spec()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    res = R.resolve(cell)
    for key in ("generator", "path"):
        assert os.path.isfile(res[key]), res[key]
    assert hasattr(R.load_module(res["generator"]), "generate")
    assert hasattr(R.load_module(res["path"]), "run")
    assert res["end_to_end"] and res["per_layer"]
    names = {m["name"] for m in res["end_to_end"] + res["per_layer"]}
    assert "setup_s" in names
    for name in names:
        mod = R.load_module(os.path.join(R.BENCH, "metrics", name + ".py"))
        assert callable(mod.read)
    assert res["limits"]


def test_every_metric_and_config_file_is_named_in_the_benchmark():
    spec = _spec()
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["source"] == c["source"]
    for m in spec["per_layer"]:
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
        for w in m["workloads"]:
            e2e = next(e for e in spec["end_to_end"] if e["name"] == m["moves"])
            assert w in e2e.get("workloads", [w])


def test_config_files_hold_what_the_program_runs():
    from chipbench.program import model_config
    for cell in CELLS:
        res = R.resolve(cell)
        cfg = res["config"]
        for path, run in cfg["run"].items():
            mc = model_config(cfg, run)
            assert (mc.d_model, mc.num_heads, mc.num_kv_heads, mc.head_dim,
                    mc.d_ff, mc.num_layers, mc.vocab_size) == (
                cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"],
                cfg["intermediate_size"], cfg["num_hidden_layers"],
                cfg["vocab_size"])
