"""The control, at the size a test run holds (14 qubits, CPU, Pallas
interpreted), judged under the limits of that size: it comes out not
correct, as on the chip at 30 qubits, where ``benchmark/control.py``
judges it under the committed limits (see PERF.md).

* rc30 cells: the reference computed in three bfloat16 passes
  (``bf16_3x``) in the program's place.  The program's own ``bf16_3x``
  window pass runs out of VMEM at 30 qubits on the chip.
* qft30.basis: the program with its ``bf16_3x`` contraction switched on.
"""

import pytest

from benchmark import run
from rehearsal_size import SMALL, small_limits
from benchmark.control import reference_control

SEED = 2 ** 31 + 7


def _run(workload):
    result, checks, _ = run.run_cell(workload, SEED, 0.5, False,
                                     require_chip=False, overrides=SMALL,
                                     limits=small_limits(workload))
    return result, checks


@pytest.mark.parametrize("workload", ["rc30.sweep", "rc30.floquet"])
def test_reference_control_is_not_correct(workload):
    result, program = _run(workload)
    assert result["correct"], program
    control = reference_control(workload, SEED, on_chip=False,
                                overrides=SMALL,
                                limits=small_limits(workload))
    assert not control["correct"], (program, control)


def test_program_control_is_not_correct():
    from quest_tpu.ops import fused

    result, program = _run("qft30.basis")
    assert result["correct"], program
    fused.set_matmul_precision("bf16_3x")
    try:
        control, checks = _run("qft30.basis")
    finally:
        fused.set_matmul_precision("highest")
    assert not control["correct"], (program, checks)
