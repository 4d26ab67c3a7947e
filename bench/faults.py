"""Faults planted under the timed path, to show that ``correct`` catches
them: each patches the program from outside for the duration of a
``with`` block.  ``bench/control.py`` reads them on the chip, the tests
on the CPU."""
from __future__ import annotations

import contextlib
from unittest import mock

import numpy as np

from bench import tables

tables.ensure_src_on_path()


@contextlib.contextmanager
def unchanged_state():
    """The fine-tune returns its state unchanged (counters still advance)."""
    from repro.core.incremental import Trainer

    def unchanged(self, entry, fs, n_active, *, in_et=None, use_lucir=False, rng=None):
        entry.step += 1
        entry.n_updates += 1
        return entry

    with mock.patch.object(Trainer, "train_group", unchanged):
        yield


@contextlib.contextmanager
def half_batch():
    """Each fine-tune batch loses its second half: the mean is over the first."""
    from repro.core.incremental import Trainer

    schedule = Trainer._train_schedule

    def half(self, n, rng):
        idx, valid, steps = schedule(self, n, rng)
        idx = idx.copy()
        h = idx.shape[1] // 2
        idx[:, h:] = idx[:, :h]
        return idx, valid, steps

    with mock.patch.object(Trainer, "_train_schedule", half):
        yield


@contextlib.contextmanager
def half_batch_some():
    """As :func:`half_batch`, in every third fine-tune only: a fault of a
    minority of rounds."""
    from repro.core.incremental import Trainer

    schedule = Trainer._train_schedule
    calls = [0]

    def half(self, n, rng):
        idx, valid, steps = schedule(self, n, rng)
        calls[0] += 1
        if calls[0] % 3 == 0:
            idx = idx.copy()
            h = idx.shape[1] // 2
            idx[:, h:] = idx[:, :h]
        return idx, valid, steps

    with mock.patch.object(Trainer, "_train_schedule", half):
        yield


@contextlib.contextmanager
def altered_prediction():
    """One prediction in fifty names the next class where it is made."""
    from repro.core.incremental import Trainer

    evaluate = Trainer.evaluate

    def altered(self, params, fs, n_active):
        correct, pred = evaluate(self, params, fs, n_active)
        pred = pred.copy()
        pred[::50] = (pred[::50] + 1) % max(int(n_active), 2)
        return correct, pred

    with mock.patch.object(Trainer, "evaluate", altered):
        yield


@contextlib.contextmanager
def altered_table():
    """Every dense export of the frequency table counts one more for its
    first predicted block."""
    from repro.core.policy import PredictionFrequencyTable as Table

    dense = Table.dense

    def altered(self, n_blocks):
        out = dense(self, n_blocks)
        hit = np.flatnonzero(out >= 0)
        if len(hit):
            out[hit[0]] += 1
        return out

    with mock.patch.object(Table, "dense", altered):
        yield


@contextlib.contextmanager
def altered_segment():
    """Every simulated segment reports its evicted-before flags inverted."""
    from repro.uvm import simulator as S

    run_segment = S.run_segment

    def altered(*a, **k):
        st, outs = run_segment(*a, **k)
        return st, dict(outs, was_evicted=np.logical_not(outs["was_evicted"]))

    with mock.patch.object(S, "run_segment", altered):
        yield


@contextlib.contextmanager
def altered_sweep():
    """Every sweep reports one more fault in its first lane."""
    from repro.uvm import simulator as S

    run_batch = S.run_batch

    def altered(trace, cells, **k):
        res = run_batch(trace, cells, **k)
        res[0] = dict(res[0], faults=res[0]["faults"] + 1)
        return res

    with mock.patch.object(S, "run_batch", altered):
        yield


LEARNED = {"unchanged_state": unchanged_state, "half_batch": half_batch, "half_batch_some": half_batch_some,
           "altered_prediction": altered_prediction, "altered_table": altered_table,
           "altered_segment": altered_segment}
SWEEP = {"altered_sweep": altered_sweep}
