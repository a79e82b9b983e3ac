"""The size the CPU rehearsal runs every cell at, and the limits it holds
them to there: float32 at 14 qubits reads state_err <= 8.5e-7 and
read_err <= 3.3e-8 over five seeds for the program (qft30.basis's
amplitude reads <= 5.6e-9), and state_err >= 4.6e-6 for every control
(the limits of the chip at 30 qubits are in ``benchmark/limits/``)."""

from benchmark import run

SMALL = {"qubits": 14, "reference_chunk_bits": 2}
SMALL_LIMITS = {"state_err": 2.5e-6, "read_err": 1.2e-7}


def small_limits(workload: str) -> dict:
    """SMALL_LIMITS for the numbers the cell compares."""
    return {k: SMALL_LIMITS[k] for k in run.load_cell(workload).limits}
