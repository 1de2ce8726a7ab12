"""Faults planted under the timed path, to show that ``correct`` catches
them.  Each is a context manager that patches the program while it is
active; the tests drive whole runs through them, and
``chipbench/control.py --fault`` reads them on the chip."""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patch(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _wrap_train_step(wrap):
    import repro.train.trainer as trainer
    make = trainer.make_train_step

    def patched(model, oc):
        return wrap(make(model, oc))

    return _patch(trainer, "make_train_step", patched)


def unchanged_state():
    """The train step returns its state as it came in."""
    def wrap(step):
        def bad(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return bad
    return _wrap_train_step(wrap)


def half_batch():
    """The train step sees only the first half of the batch's rows, and
    takes its mean over them."""
    def wrap(step):
        def bad(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return bad
    return _wrap_train_step(wrap)


def altered_token():
    """The decode step hands back a wrong token for its first active row."""
    from repro.serve.engine import ModelBackend
    decode = ModelBackend.decode_step

    def bad(self, page_table, cur_lens, active, tokens, rids):
        import numpy as np
        nt = np.array(decode(self, page_table, cur_lens, active, tokens,
                             rids))
        rows = np.nonzero(active)[0]
        if len(rows):
            nt[rows[0]] = (nt[rows[0]] + 1) % self.model.cfg.vocab_size
        return nt

    return _patch(ModelBackend, "decode_step", bad)


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "altered_token": altered_token}
