"""Each traffic generator is a pure function of the seed, and every seed
gets the same lengths."""
import numpy as np
import pytest

from chipbench import run as R

BIG = 2 ** 31 + 12345


def _gen(cell, seed, seconds=30.0):
    res = R.resolve(cell)
    return R.load_module(res["generator"]).generate(
        res["traffic"], res["config"], seed, seconds)


def _key(out):
    if isinstance(out, np.ndarray):
        return out.tobytes()
    return [(q["rid"], q["due"], q["gen"], q["prompt"].tobytes()) for q in out]


@pytest.mark.parametrize("cell", ["train.smollm-360m.s4096",
                                  "serve.smollm-360m.chat",
                                  "serve.h2o-danube3-4b.batch"])
def test_generator_is_a_pure_function_of_the_seed(cell):
    a, b, c = _gen(cell, BIG), _gen(cell, BIG), _gen(cell, BIG + 1)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("cell", ["serve.smollm-360m.chat",
                                  "serve.h2o-danube3-4b.batch"])
def test_every_seed_gets_the_same_lengths(cell):
    a, b = _gen(cell, 7), _gen(cell, BIG)
    for f in (lambda q: len(q["prompt"]), lambda q: q["gen"]):
        assert sorted(map(f, a)) == sorted(map(f, b))
    assert max(q["due"] for q in a) == pytest.approx(max(q["due"] for q in b))


def test_chat_arrivals_fill_the_window_at_the_rate():
    res = R.resolve("serve.smollm-360m.chat")
    out = _gen("serve.smollm-360m.chat", 3, seconds=30.0)
    assert len(out) == round(res["traffic"]["rate"] * 30.0)
    assert max(q["due"] for q in out) < 30.0
    p = res["traffic"]["prompt"]
    assert all(p["min"] <= len(q["prompt"]) <= p["max"] for q in out)
