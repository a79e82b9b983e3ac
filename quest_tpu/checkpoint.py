"""Checkpoint / resume: durable snapshots of registers and operators.

The reference's persistence story is minimal — a per-rank CSV dump
(reportState, QuEST_common.c:229-245), a debug-only CSV loader
(initStateFromSingleFile, QuEST_cpu.c:1680-1729) and amplitude get/set
APIs users must script themselves (SURVEY.md §5.4).  This module exceeds
that: orbax-backed save/restore of the (possibly sharded) amplitude array
with metadata, so a multi-device register round-trips with its sharding
reconstructed on the current mesh — plus CSV read/write kept for
reference-format compatibility.
"""

from __future__ import annotations

import json
import math
import os

import jax
import numpy as np

from .env import QuESTEnv
from .qureg import Qureg
from .validation import QuESTError

_META_NAME = "qureg_meta.json"
_AMPS_NAME = "amps"


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.StandardCheckpointer()


def _qureg_meta(qureg: Qureg) -> dict:
    """Base register metadata (the resilience layer extends it with a
    circuit cursor, the live permutation, and the RNG state)."""
    from . import precision

    return {
        "num_qubits_represented": qureg.num_qubits_represented,
        "is_density_matrix": qureg.is_density_matrix,
        "dtype": str(np.dtype(qureg.dtype)),
        "precision": precision.get_precision(),
        "mesh_shards": qureg.num_chunks,
        # 0 = scalar register; B >= 1 = a BatchedQureg bank of B elements
        # (batch.py) whose payload is (B, 2, 2^n)
        "batch": int(getattr(qureg, "batch_size", 0) or 0),
    }


def _write_meta(path: str, meta: dict) -> None:
    tmp = os.path.join(path, _META_NAME + ".tmp")
    with open(tmp, "w") as f:
        json.dump(meta, f)
    os.replace(tmp, os.path.join(path, _META_NAME))


def _read_meta(path: str) -> dict:
    meta_path = os.path.join(path, _META_NAME)
    if not os.path.exists(meta_path):
        raise FileNotFoundError(f"no qureg checkpoint at {path}")
    with open(meta_path) as f:
        meta = json.load(f)
    if not isinstance(meta, dict) or "num_qubits_represented" not in meta:
        raise ValueError(f"malformed checkpoint metadata at {meta_path}")
    return meta


def _qureg_from_meta(meta: dict, env: QuESTEnv) -> Qureg:
    """Build the target register for a restore, validating the checkpoint
    against THIS env up front — a precision or shardability mismatch must
    surface as a structured QuESTError naming both sides, not as an orbax
    resharding failure deep inside the restore."""
    from . import precision

    ck_dtype = np.dtype(meta["dtype"])
    env_dtype = precision.real_dtype()
    if ck_dtype != np.dtype(env_dtype):
        raise QuESTError(
            "loadQureg: checkpoint precision mismatch — the checkpoint "
            f"was written at dtype {ck_dtype} (precision "
            f"{meta.get('precision', '?')}) but this environment runs at "
            f"dtype {np.dtype(env_dtype)} (precision "
            f"{precision.get_precision()}); call set_precision to match "
            "before loading"
        )
    batch = int(meta.get("batch", 0) or 0)
    if batch:
        from .batch import BatchedQureg

        q = BatchedQureg(meta["num_qubits_represented"], env, batch,
                         is_density_matrix=meta["is_density_matrix"])
    else:
        q = Qureg(meta["num_qubits_represented"], env,
                  meta["is_density_matrix"])
    if q.num_amps_total < env.num_devices:
        raise QuESTError(
            "loadQureg: the mesh has grown past the register's shardable "
            f"size — the checkpoint holds {q.num_amps_total} amplitudes "
            f"({meta['num_qubits_represented']} qubits, density="
            f"{meta['is_density_matrix']}) but this environment has "
            f"{env.num_devices} devices; load on a mesh with at most "
            f"{q.num_amps_total} devices"
        )
    q.dtype = ck_dtype
    return q


def _restore_amps(path: str, q: Qureg):
    """Restore the amplitude payload for ``q`` from ``path`` (transient IO
    errors retried with bounded exponential backoff)."""
    from . import resilience

    ckpt = _checkpointer()
    batch = int(getattr(q, "batch_size", 0) or 0)
    shape = (batch, 2, q.num_amps_total) if batch else (2, q.num_amps_total)
    target = jax.ShapeDtypeStruct(shape, q.dtype, sharding=q.sharding())
    restored = resilience.retry_io(
        ckpt.restore, os.path.join(path, _AMPS_NAME), {"amps": target},
        what="loadQureg(amps)")
    return restored["amps"]


def saveQureg(qureg: Qureg, path: str) -> None:
    """Write a durable snapshot of ``qureg`` (amps + metadata) at ``path``.

    Works for state-vectors and density matrices, any sharding; the write
    is atomic at the directory level (orbax finalization), and transient
    IO errors are retried with bounded exponential backoff
    (resilience.retry_io).  Amplitudes are written in CANONICAL qubit
    order (any live permutation rematerializes first); the resilience
    layer's generation protocol (resilience.save_generation) instead
    snapshots the raw permuted state for bit-exact mid-circuit resume."""
    from . import resilience

    path = os.path.abspath(path)
    ckpt = _checkpointer()
    resilience.retry_io(
        ckpt.save, os.path.join(path, _AMPS_NAME), {"amps": qureg.amps},
        force=True, what="saveQureg(amps)")
    resilience.retry_io(ckpt.wait_until_finished, what="saveQureg(wait)")
    resilience.retry_io(_write_meta, path, _qureg_meta(qureg),
                        what="saveQureg(meta)")


def loadQureg(path: str, env: QuESTEnv, *, strict_mesh: bool = False) -> Qureg:
    """Restore a register saved by :func:`saveQureg` onto ``env``'s mesh.

    The amplitude array is restored directly into the register's current
    sharding (resharding on the fly if the mesh shape changed).  The
    checkpoint metadata is validated against ``env`` FIRST: a precision
    mismatch (e.g. written at prec 2, loaded at prec 1) raises a
    QuESTError naming both sides instead of failing inside orbax
    resharding.

    When the mesh has grown past the register's shardable size (more
    devices than amplitudes), the default is ELASTIC: the environment
    auto-shrinks to the largest usable device subset (env.shrink_env,
    recorded in the degradation registry) and the register loads onto
    that degraded mesh — its ``env`` attribute names the shrunken
    environment.  ``strict_mesh=True`` restores the old structured
    error, and additionally refuses ANY shard-count difference from the
    writing mesh (recorded in the checkpoint metadata)."""
    from . import resilience, telemetry

    path = os.path.abspath(path)
    try:
        meta = _read_meta(path)
    except FileNotFoundError:
        raise QuESTError(f"no qureg checkpoint at {path}", "loadQureg")
    saved_shards = meta.get("mesh_shards")
    if strict_mesh and saved_shards is not None \
            and int(saved_shards) != env.num_devices:
        raise QuESTError(
            "loadQureg: checkpoint mesh mismatch — written on "
            f"{saved_shards} shards but this environment has "
            f"{env.num_devices} devices, and strict_mesh=True refuses "
            "elastic restore")
    n_sv = (2 if meta.get("is_density_matrix") else 1) \
        * int(meta["num_qubits_represented"])
    total = 1 << n_sv
    if not strict_mesh and total < env.num_devices:
        from . import env as _env_mod

        shrunk = _env_mod.shrink_env(env, total)
        resilience.record_degradation(
            f"loadQureg_mesh_{env.num_devices}to{total}",
            f"the mesh ({env.num_devices} devices) has grown past the "
            f"register's shardable size ({total} amplitudes); loaded "
            f"onto a {total}-device sub-mesh")
        env = shrunk
    if saved_shards is not None and int(saved_shards) != env.num_devices:
        telemetry.inc("elastic_restores_total")
    q = _qureg_from_meta(meta, env)
    q.amps = _restore_amps(path, q)
    return q


# ---------------------------------------------------------------------------
# Reference-format CSV ("re, im" per line, '#' comments) — the format
# reportState writes and initStateFromSingleFile reads in the reference.
# ---------------------------------------------------------------------------


def writeStateToFile(qureg: Qureg, filename: str) -> None:
    """Dump amplitudes as reference-style CSV (QuEST_common.c:229-245).

    Streams tile-aligned 2^14-amp blocks to disk (element.get_block_host)
    instead of gathering the whole state into one host buffer, matching
    the reference's per-rank chunked reportState — so large states keep
    CSV export with no max_amps_in_msg cap (ADVICE r4)."""
    from .ops import element

    total = qureg.num_amps_total
    amps = qureg.amps
    if amps.ndim != 4 and amps.shape[1] >= element.BLK:
        # canonical 4-d view first: a raw flat block offset overflows
        # int32 at >= 2^31 amps in x64-off mode (element.py:_as_canonical)
        amps = element._as_canonical(amps)
    # fetch in multi-block chunks: each device->host round trip has a
    # fixed cost, and per-2^14-block fetches would number 2^16 at 2^30
    # amps; 2^10 blocks (2^24 amps, ~128-256 MB host) keeps memory
    # bounded while cutting the fetch count ~1000x
    chunk_blocks = 1 << 10
    with open(filename, "w") as f:
        f.write("# quest_tpu state dump: re, im per amplitude\n")
        written = 0
        nblocks = (total + element.BLK - 1) // element.BLK
        for b0 in range(0, nblocks, chunk_blocks):
            nb = min(chunk_blocks, nblocks - b0)
            if amps.ndim == 4:
                part = np.asarray(jax.lax.dynamic_slice_in_dim(
                    amps, b0, nb, axis=1)).reshape(2, -1)
            else:
                part = np.asarray(jax.lax.dynamic_slice(
                    amps, (0, b0 * element.BLK),
                    (2, min(nb * element.BLK, amps.shape[1]))))
            m = min(part.shape[1], total - written)
            for k in range(m):
                f.write(f"{float(part[0, k])!r}, {float(part[1, k])!r}\n")
            written += m


# amps per streamed read chunk: 2^20 f64 pairs = 16 MB host buffer, and
# each chunk is one tile-aligned ranged write (element.set_amp_range)
_READ_CHUNK = 1 << 20


def readStateFromFile(qureg: Qureg, filename: str) -> bool:
    """Load amplitudes from reference-style CSV; returns success
    (statevec_initStateFromSingleFile, QuEST_cpu.c:1680-1729).

    Streams the file in tile-aligned chunks through ranged device writes
    (element.set_amp_range) into a fresh device-side buffer — the
    register is only rebound on full success, so failure semantics are
    unchanged (malformed/truncated/garbage file leaves the state
    untouched — the stream writes into a fresh device buffer, never the
    live register).  Non-finite values (NaN/Inf — a torn write or bit
    rot, never a legal amplitude) are rejected like any other parse
    failure.  No full-state host buffer is ever built, restoring
    round-trip symmetry with the streamed writeStateToFile: any state
    that module can dump, this can load (the old path hard-failed via
    _guard_host_gather beyond the message cap — ADVICE r5)."""
    import jax.numpy as jnp

    from .ops import element

    if not os.path.exists(filename):
        return False
    total = qureg.num_amps_total
    work = jax.device_put(
        jnp.zeros((2, total), qureg.dtype), qureg.sharding())
    buf = np.zeros((2, _READ_CHUNK))
    fill = 0          # valid amps in buf
    written = 0       # amps flushed to the device
    try:
        with open(filename) as f:
            for line in f:
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                if written + fill >= total:
                    break
                parts = line.split(",")
                re, im = float(parts[0]), float(parts[1])
                if not (math.isfinite(re) and math.isfinite(im)):
                    return False
                buf[0, fill], buf[1, fill] = re, im
                fill += 1
                if fill == _READ_CHUNK:
                    work = element.set_amp_range(work, written,
                                                 buf.astype(qureg.dtype))
                    written += fill
                    fill = 0
    except (ValueError, IndexError):
        return False  # malformed line: report failure, leave state untouched
    if fill:
        work = element.set_amp_range(work, written,
                                     buf[:, :fill].astype(qureg.dtype))
        written += fill
    if written < total:
        return False  # truncated file
    qureg.amps = work
    return True
