"""Operation and byte counts against hand counts at the cells' shapes."""
import json
import os

import pytest

from chipbench import flops
from chipbench.reference.dense import Dims

CFG = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def dims(name):
    with open(os.path.join(CFG, name + ".json")) as f:
        return Dims.from_config(json.load(f))


def test_smollm_train_flops_by_hand():
    dm = dims("smollm-360m")
    # per layer: q,k,v 960*(15+5+5)*64, o 15*64*960, mlp 3*960*2560
    per_layer = 960 * 25 * 64 + 15 * 64 * 960 + 3 * 960 * 2560
    assert per_layer == 9_830_400
    n = 32 * per_layer + 960 * 49152          # tied unembedding
    assert n == 361_758_720
    attn = 3 * 4 * 15 * 64 * 32 * 4097 / 2    # fwd+bwd, causal mean keys
    assert flops.train_flops_per_token(dm, 4096) == pytest.approx(6 * n + attn)
    # one B=12, S=4096 step: the 1.44e14 FLOPs the smoke run quoted
    assert flops.train_flops_per_token(dm, 4096) * 12 * 4096 == \
        pytest.approx(1.44e14, rel=5e-3)
    assert flops.param_count(dm) == n + 65 * 960


def test_danube_counts_by_hand():
    dm = dims("h2o-danube3-4b")
    per_layer = 3840 * (32 + 16) * 120 + 32 * 120 * 3840 + 3 * 3840 * 10240
    assert flops.param_count(dm) == (24 * per_layer + 2 * 3840 * 32000
                                     + 49 * 3840)
    assert flops.param_count(dm) == pytest.approx(3.96e9, rel=5e-3)
    # one decode step, two rows at 100 and 300 cached tokens, bf16
    ops, nbytes = flops.decode_step(dm, [100, 300], 2, 2)
    mm = 24 * per_layer + 3840 * 32000
    keys = 101 + 301
    assert ops == 2 * mm * 2 + 4 * 32 * 120 * 24 * keys
    assert nbytes == mm * 2 + keys * 2 * 24 * 8 * 120 * 2
    # weights alone at 819 GB/s: ~9.7 ms, bandwidth-bound
    assert flops.least_time(ops, nbytes, 197e12, 819e9) == \
        pytest.approx(nbytes / 819e9)
    assert 9.0e-3 < nbytes / 819e9 < 10.0e-3


def test_flash_counts_by_hand():
    ops, nbytes = flops.flash_fwd(1, 2, 1, 4, 8, 2)
    assert ops == 4 * 2 * 8 * 10                # 10 causal pairs of 4
    assert nbytes == (2 * 2 * 4 * 8 + 2 * 1 * 4 * 8) * 2 + 4 * 2 * 4
    ops_b, _ = flops.flash_bwd(1, 2, 1, 4, 8, 2)
    assert ops_b == 2.5 * ops


def test_prefill_counts_true_length():
    dm = dims("smollm-360m")
    assert flops.prefill_flops(dm, 1) == pytest.approx(
        2 * 32 * 9_830_400 + 2 * 960 * 49152 + 4 * 15 * 64 * 32)
