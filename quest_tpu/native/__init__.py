"""ctypes binding + on-demand build of the native C++ circuit scheduler.

The library (scheduler.cc) is compiled with g++ into _qts.so next to this
file, and rebuilt whenever the source's content hash differs from the one
recorded beside the library (_qts.so.sha256) — a library built from other
source is never loaded; if the toolchain is unavailable the import degrades
gracefully
and circuit.py falls back to its Python planner (same algorithm — the
native path exists for million-gate streams where per-gate Python
bookkeeping dominates).  Disable with QT_NATIVE=0.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "scheduler.cc")
_LIB = os.path.join(_DIR, "_qts.so")
_LIB_HASH = _LIB + ".sha256"

_lock = threading.Lock()
_lib = None
_build_failed = False


def _source_hash() -> Optional[str]:
    try:
        with open(_SRC, "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()
    except OSError:
        return None


def _built_hash() -> Optional[str]:
    try:
        with open(_LIB_HASH) as f:
            return f.read().strip()
    except OSError:
        return None


def _build(digest: str) -> bool:
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, _LIB)  # atomic: concurrent readers never see a torn .so
        with open(tmp, "w") as f:
            f.write(digest)
        os.replace(tmp, _LIB_HASH)
        return True
    except (subprocess.SubprocessError, OSError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def get_lib():
    """Load (building if needed) the native scheduler; None if unavailable."""
    global _lib, _build_failed
    if os.environ.get("QT_NATIVE", "1") == "0":
        return None
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        digest = _source_hash()
        if digest is None and not os.path.exists(_LIB):
            _build_failed = True
            return None
        if digest is not None and (not os.path.exists(_LIB)
                                   or _built_hash() != digest):
            if not _build(digest):
                _build_failed = True
                return None
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError:
            _build_failed = True
            return None
        lib.qts_plan.restype = ctypes.c_int
        lib.qts_plan.argtypes = [
            ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.qts_free.restype = None
        lib.qts_free.argtypes = [ctypes.POINTER(ctypes.c_int64)]
        try:
            lib.qts_plan_windowed.restype = ctypes.c_int
            lib.qts_plan_windowed.argtypes = [
                ctypes.c_int64, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
                ctypes.POINTER(ctypes.c_int64),
            ]
        except AttributeError:  # older _qts.so without the windowed planner
            pass
        _lib = lib
        return _lib


def native_available() -> bool:
    return get_lib() is not None


def plan_native_windowed(target_lists: Sequence[Sequence[int]],
                         num_qubits: int,
                         xranks: Sequence[int],
                         flags: Optional[Sequence[int]] = None,
                         ) -> Optional[List[tuple]]:
    """Run the C++ windowed planner (qts_plan_windowed) over gate target
    lists + per-gate cross ranks and diagonality flags (bit 0 = diagonal
    matrix, bit 1 = diagonal 2q, mask-foldable when crossing).  Returns a
    structural plan —
      ('winfused', k, [(kind, gate_idx, bits), ...])  kind: 0=A, 1=B,
        2=cross, 3=mask, both with bits=(lane_bit, win_bit, lane_is_bit0)
      ('apply', gate_idx, targets)
    — or None when the native library (or entry point) is unavailable."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "qts_plan_windowed"):
        return None
    offsets = np.zeros(len(target_lists) + 1, dtype=np.int64)
    for i, t in enumerate(target_lists):
        offsets[i + 1] = offsets[i] + len(t)
    flat = np.fromiter(
        (q for t in target_lists for q in t), dtype=np.int64,
        count=int(offsets[-1]),
    )
    if flat.size == 0:
        flat = np.zeros(1, dtype=np.int64)
    xr = np.asarray(list(xranks), dtype=np.int64)
    if xr.size == 0:
        xr = np.zeros(1, dtype=np.int64)
    if flags is None:
        flags = [0] * len(target_lists)
    fl = np.asarray(list(flags), dtype=np.int64)
    if fl.size == 0:
        fl = np.zeros(1, dtype=np.int64)
    buf = ctypes.POINTER(ctypes.c_int64)()
    length = ctypes.c_int64()
    rc = lib.qts_plan_windowed(
        num_qubits, len(target_lists),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        xr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        fl.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.byref(buf), ctypes.byref(length),
    )
    if rc != 0:
        return None
    try:
        data = np.ctypeslib.as_array(buf, shape=(length.value,)).copy()
    finally:
        lib.qts_free(buf)

    ops: List[tuple] = []
    i = 1
    for _ in range(int(data[0])):
        kind = int(data[i]); i += 1
        if kind == 4:
            k = int(data[i]); nf = int(data[i + 1]); i += 2
            entries = []
            for _f in range(nf):
                side = int(data[i]); gi = int(data[i + 1])
                nb = int(data[i + 2]); i += 3
                bits = tuple(int(b) for b in data[i:i + nb]); i += nb
                entries.append((side, gi, bits))
            ops.append(("winfused", k, entries))
        elif kind == 1:
            gi = int(data[i]); nt = int(data[i + 1]); i += 2
            targs = tuple(int(p) for p in data[i:i + nt]); i += nt
            ops.append(("apply", gi, targs))
        else:
            raise ValueError(f"bad windowed plan op kind {kind}")
    return ops


def plan_native(target_lists: Sequence[Sequence[int]],
                num_qubits: int) -> Optional[List[tuple]]:
    """Run the C++ planner over gate target lists.

    Returns a *structural* plan — ops referencing gates by index:
      ('fused', [(side, gate_idx, bits), ...])   side: 0=A, 1=B, 2=cross
      ('apply', gate_idx, phys_targets)
      ('segswap', a, b, m)
    or None when the native library is unavailable.
    """
    lib = get_lib()
    if lib is None:
        return None
    offsets = np.zeros(len(target_lists) + 1, dtype=np.int64)
    for i, t in enumerate(target_lists):
        offsets[i + 1] = offsets[i] + len(t)
    flat = np.fromiter(
        (q for t in target_lists for q in t), dtype=np.int64,
        count=int(offsets[-1]),
    )
    if flat.size == 0:
        flat = np.zeros(1, dtype=np.int64)  # valid pointer for ctypes
    buf = ctypes.POINTER(ctypes.c_int64)()
    length = ctypes.c_int64()
    rc = lib.qts_plan(
        num_qubits, len(target_lists),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.byref(buf), ctypes.byref(length),
    )
    if rc != 0:
        return None
    try:
        data = np.ctypeslib.as_array(buf, shape=(length.value,)).copy()
    finally:
        lib.qts_free(buf)

    ops: List[tuple] = []
    i = 1
    for _ in range(int(data[0])):
        kind = int(data[i]); i += 1
        if kind == 0:
            nf = int(data[i]); i += 1
            entries = []
            for _f in range(nf):
                side = int(data[i]); gi = int(data[i + 1])
                k = int(data[i + 2]); i += 3
                bits = tuple(int(b) for b in data[i:i + k]); i += k
                entries.append((side, gi, bits))
            ops.append(("fused", entries))
        elif kind == 1:
            gi = int(data[i]); k = int(data[i + 1]); i += 2
            phys = tuple(int(p) for p in data[i:i + k]); i += k
            ops.append(("apply", gi, phys))
        elif kind == 2:
            k = int(data[i]); i += 1
            perm = tuple(int(p) for p in data[i:i + k]); i += k
            ops.append(("permute", perm))
        elif kind == 3:
            a = int(data[i]); b = int(data[i + 1]); m = int(data[i + 2]); i += 3
            ops.append(("segswap", a, b, m))
        else:
            raise ValueError(f"bad plan op kind {kind}")
    return ops
