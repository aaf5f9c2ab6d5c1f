"""The rest of a run with the timed path broken underneath: ``correct``
has to come out false, once for each fault a cell can have. The faults
are planted between set-up and the window, in the program's own objects:

- ``state_unchanged``: the jitted step's answers are kept, the state it
  returns is thrown away (rings and NFA slots stay as set-up left them);
- ``half_batch``: the entry gets the first half of every batch's rows;
- ``answer_altered``: one value of one output column is changed where
  the step produces it, before the meta pull and the callback.

These cells run on one chip, so there is no exchange to leave out.
"""

import numpy as np
import pytest

from conftest import CELLS

from benchmarks import drive


def _steps(rt):
    """(holder dict-or-object, key) of every jitted step the app built."""
    found = []
    for qr in rt.query_runtimes.values():
        if getattr(qr, "_steps", None):          # NFA: one step a stream
            found += [(qr._steps, k) for k in qr._steps]
        elif getattr(qr, "_step", None) is not None:
            found.append((qr.__dict__, "_step"))
    assert found
    return found


def _wrap_steps(rt, wrapper):
    for holder, key in _steps(rt):
        holder[key] = wrapper(holder[key])


def state_unchanged(sender, config):
    import jax
    import jax.numpy as jnp

    def wrapper(real):
        def step(state, cols, now):
            kept = jax.tree_util.tree_map(jnp.copy, state)  # it is donated
            _new, out = real(state, cols, now)
            return kept, out
        return step
    _wrap_steps(sender.rt, wrapper)


def answer_altered(sender, config):
    column = [a for r, a in config["output"]["columns"].items()
              if r != "key"][0]

    def wrapper(real):
        def step(state, cols, now):
            new, out = real(state, cols, now)
            out = dict(out)
            first = out["__valid__"].argmax()     # one answer, a valid one
            out[column] = out[column].at[first].add(1.0)
            return new, out
        return step
    _wrap_steps(sender.rt, wrapper)


def half_batch(sender, config):
    for h in sender.handlers:
        real = h.send_columns

        def send(data, timestamps=None, real=real):
            half = len(timestamps) // 2
            return real({k: v[:half] for k, v in data.items()},
                        timestamps=timestamps[:half])
        h.send_columns = send


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   answer_altered])
@pytest.mark.parametrize("workload", CELLS)
def test_a_broken_timed_path_is_not_correct(run_cell, monkeypatch, workload,
                                            fault):
    real_window = drive.run_window
    planted = []

    def window(sender, *args, **kw):
        from benchmarks import manifest

        fault(sender, manifest.Cell(workload).config)
        planted.append(fault.__name__)
        return real_window(sender, *args, **kw)

    monkeypatch.setattr(drive, "run_window", window)
    # long enough that batches go on after the compile a new shape (a
    # half batch, an altered output) costs inside the window
    rc, last, cap = run_cell(workload, "--trace", "0", "--cpu-rehearsal",
                             seconds=2)
    assert planted == [fault.__name__]
    assert rc == 0 and last["correct"] is False, last
    over = {n for n, p in last["compared"].items()
            if p["value"] > p["limit"]}
    # the fault shows in the answers, not in the harness's own checks
    assert over & {"rows_missing", "sum_mismatch_rows", "avg_max_abs_err",
                   "v2_max_rel_err", "v1_max_rel_err",
                   "key_mismatch_rows"}, last["compared"]
    assert "<-- over" in cap.err


def test_without_a_fault_the_same_run_is_correct(run_cell):
    rc, last, _ = run_cell(CELLS[0], "--trace", "0", "--cpu-rehearsal")
    assert rc == 0 and last["correct"] is True


def test_open_loop_stamps_the_due_time_and_reports_lateness():
    """The generator honours a rate though no cell of this PR sets one:
    batch k is due at k / rate and is stamped with that, not with the
    time it went."""
    import time

    class Slow:
        created = {}

        def send(self, i, due=None):
            self.created[i] = due
            time.sleep(0.03)          # slower than the 100/s asked for

    s = Slow()
    t0, n, late = drive.run_window(s, 5, 0.2, rate=100.0)
    assert n - 5 == 20                # all 20 due batches went, late or not
    dues = np.array([s.created[i] for i in range(5, n)])
    assert np.allclose(np.diff(dues), 0.01)
    assert late > 0.2                 # and it says how late it ran
