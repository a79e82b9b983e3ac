"""A run with the timed path broken underneath has to come out not
correct.  Each test drives the whole of ``run.run_cell`` except the look
for a chip, at 14 qubits on the CPU, with one fault planted in the
program:

* a step that returns its state unchanged: the gate drain (``fusion._run``)
  or ``applyFullQFT`` does nothing;
* an answer altered where it is produced: the read returns its value
  moved by a small amount, or, in a cell that compares no read, every
  rotateX's output is moved.

The cells run on one chip, so the faults of a batch or of the exchange
between chips do not arise.
"""

import json
import os

import pytest

from benchmark import run
from rehearsal_size import SMALL, SMALL_LIMITS, small_limits

SEED = 2 ** 31 + 99
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _workloads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [(w["name"], w["config"]) for w in json.load(f)["workloads"]]


def _run(workload):
    result, checks, _ = run.run_cell(workload, SEED, 1.0, False,
                                     require_chip=False, overrides=SMALL,
                                     limits=small_limits(workload))
    return result, checks


@pytest.mark.parametrize("workload,config", _workloads())
def test_state_left_unchanged_is_not_correct(workload, config,
                                             monkeypatch):
    import quest_tpu as qt
    from quest_tpu import fusion

    if run.load_cell(workload).cfg["family"] == "qft":
        monkeypatch.setattr(qt, "applyFullQFT", lambda q: None)
    else:
        monkeypatch.setattr(fusion, "_run", lambda qureg, items: None)
    result, checks = _run(workload)
    assert not result["correct"], checks


@pytest.mark.parametrize("workload,config", _workloads())
def test_altered_answer_is_not_correct(workload, config, monkeypatch):
    """The read moved where it is produced (the ``qt`` function its read
    module names); in a cell that compares no read (rc30.sweep, see
    PERF.md), every rotateX's output moved instead (its angle off by
    1e-3)."""
    import quest_tpu as qt

    cell = run.load_cell(workload, overrides=SMALL)
    if "read_err" not in cell.limits:
        orig = qt.rotateX
        monkeypatch.setattr(qt, "rotateX",
                            lambda q, t, a: orig(q, t, a + 1e-3))
        name = "state_err"
    else:
        api = run.Stream(cell.mix, cell.family, SEED).read_mod.API
        orig = getattr(qt, api)
        delta = 4 * SMALL_LIMITS["read_err"]
        monkeypatch.setattr(qt, api, lambda *a: orig(*a) + delta)
        name = "read_err"
    result, checks = _run(workload)
    assert not result["correct"], checks
    assert checks[name]["value"] > checks[name]["limit"]
