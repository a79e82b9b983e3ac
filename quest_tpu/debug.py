"""Debug / test-support API — the reference's QuEST_debug.h surface.

Non-public hooks the reference exposes for its own test harness
(QuEST/src/QuEST_debug.h): single-qubit classical init, state file
loading, and amp-wise state comparison.  ``initDebugState`` and
``setDensityAmps`` live in the main API (api.py) as in the reference.
"""

from __future__ import annotations

import math

import numpy as np

from . import validation as V
from .checkpoint import readStateFromFile
from .env import QuESTEnv
from .qureg import Qureg


def initStateOfSingleQubit(qureg: Qureg, qubitId: int, outcome: int) -> None:
    """Uniform superposition over all basis states whose ``qubitId`` bit
    equals ``outcome`` (statevec_initStateOfSingleQubit,
    QuEST_cpu.c — normFactor 1/sqrt(2^n / 2))."""
    V.validate_target(qureg, qubitId, "initStateOfSingleQubit")
    V.validate_outcome(outcome, "initStateOfSingleQubit")
    n = qureg.num_qubits_in_state_vec
    dim = 1 << n
    norm = 1.0 / math.sqrt(dim / 2.0)
    idx = np.arange(dim)
    re = np.where(((idx >> qubitId) & 1) == outcome, norm, 0.0)
    qureg.amps = qureg.device_put(np.stack([re, np.zeros(dim)]))


def initStateFromSingleFile(qureg: Qureg, filename: str,
                            env: QuESTEnv | None = None) -> bool:
    """Load amplitudes from a reference-format CSV file; returns success
    (statevec_initStateFromSingleFile, QuEST_cpu.c:1680-1729)."""
    return readStateFromFile(qureg, filename)


def _guard_host_gather(qureg: Qureg, func: str) -> None:
    """Refuse to gather a full state to one host buffer beyond the
    reference's message cap (MPI_MAX_AMPS_IN_MSG — the reference's
    toQVector guard, utilities.cpp:1073-1074): at 30q+ the gather is also
    a full-state device layout copy, which a 30q state cannot afford."""
    from .precision import max_amps_in_msg

    if qureg.num_amps_total > max_amps_in_msg():
        raise V.QuESTError(
            f"{func}: State has too many amplitudes "
            f"({qureg.num_amps_total} > {max_amps_in_msg()}) to gather to "
            "a single host buffer; use getAmp/reportState per chunk "
            "instead.")


def compareStates(qureg1: Qureg, qureg2: Qureg, precision: float) -> bool:
    """Amp-wise |re1-re2|, |im1-im2| <= precision on every amplitude
    (statevec_compareStates, QuEST_cpu.c)."""
    if qureg1.num_qubits_in_state_vec != qureg2.num_qubits_in_state_vec:
        return False
    _guard_host_gather(qureg1, "compareStates")
    a = np.asarray(qureg1.amps)
    b = np.asarray(qureg2.amps)
    return bool(np.all(np.abs(a - b) <= precision))
