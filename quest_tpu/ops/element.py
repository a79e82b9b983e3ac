"""Layout-safe element access: jitted slice kernels on the canonical view.

getAmp-class reads and setAmps-class writes must never trigger a
full-state re-layout: an eager ``amps[:, index]`` on a canonically-tiled
28q+ state makes XLA first copy the WHOLE state into the default flat
layout (a second state, which does not fit beside a 30q one) — where
the reference's getAmp is an O(1) chunk read (QuEST.h:1987,
QuEST_cpu_local.c:225-233).

The kernels here dynamic-slice the canonical (2, 2^(n-14), 128, 128)
view — a free bitcast at the jit boundary for canonically-held states
(circuit.canonical_view) — touching one 128x128 tile per access; flat
(2, 2^n) registers take an equivalent flat dynamic-slice.  Index
components enter as traced scalars, so repeated accesses never
recompile.  Writes decompose a contiguous range into tile-aligned whole
blocks (one dynamic_update_slice) plus at most two edge blocks handled
read-modify-write, one tile each.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .fused import CLUSTER_DIM as DIM, CLUSTER_QUBITS as BLK_BITS

BLK = 1 << BLK_BITS  # amps per canonical block (one 128x128 tile pair)


@jax.jit
def _get_pair_canonical(v, b, s, l):
    return jax.lax.dynamic_slice(v, (0, b, s, l), (2, 1, 1, 1)).reshape(2)


@jax.jit
def _get_pair_flat(v, i):
    return jax.lax.dynamic_slice(v, (0, i), (2, 1))[:, 0]


@jax.jit
def _get_block(v, b):
    return jax.lax.dynamic_slice(
        v, (0, b, 0, 0), (2, 1, DIM, DIM)).reshape(2, DIM, DIM)


@partial(jax.jit, donate_argnums=0)
def _set_blocks(v, blocks, b0):
    return jax.lax.dynamic_update_slice(v, blocks, (0, b0, 0, 0))


@partial(jax.jit, donate_argnums=0)
def _set_flat(v, vals, i):
    return jax.lax.dynamic_update_slice(v, vals, (0, i))


def _as_canonical(amps):
    """Reshape a flat (2, N >= 2^14) register to the canonical 4-d view
    (a bitcast for row-major layouts).  Index components into the 4-d
    view stay < 2^31 for any register size, so traced indices never
    overflow int32 in single-precision (x64-off) mode — a raw flat index
    would at >= 2^31 amps (e.g. a 16q density matrix)."""
    return amps.reshape(2, -1, DIM, DIM)


def _owner_shard(amps, block: int):
    """(shard data, local block) of the device holding canonical block
    ``block`` of a state sharded on its block axis.  A dynamic slice of
    the sharded array would make XLA gather the whole state to every
    device (32 GiB at 32 qubits over four chips); the owner's own shard
    is read on its device instead."""
    for shard in amps.addressable_shards:
        rows = shard.index[1]
        start = rows.start or 0
        stop = amps.shape[1] if rows.stop is None else rows.stop
        if start <= block < stop:
            return shard.data, block - start
    raise ValueError(f"block {block} is on no device of this process")


def get_amp_pair(amps, index: int):
    """(re, im) device pair of amplitude ``index`` without any re-layout.
    Accepts the flat (2, 2^n) register form or the canonical 4-d view the
    chained big-state executor keeps (circuit.canonical_view)."""
    if amps.ndim != 4:
        if amps.shape[1] < BLK:
            return _get_pair_flat(amps, index)
        amps = _as_canonical(amps)
    block = index >> BLK_BITS
    if isinstance(amps, jax.Array) and len(amps.sharding.device_set) > 1:
        amps, block = _owner_shard(amps, block)
    return _get_pair_canonical(amps, block, (index >> 7) & (DIM - 1),
                               index & (DIM - 1))


def get_block_host(amps, b: int) -> np.ndarray:
    """One canonical 2^14-amp block as a host (2, 2^14) array (a single
    tile-aligned device read — used by streamed reportState and the edge
    blocks of set_amp_range)."""
    if amps.ndim == 4:
        return np.array(_get_block(amps, b)).reshape(2, BLK)
    lo = b * BLK
    return np.array(
        jax.lax.dynamic_slice(amps, (0, lo), (2, min(BLK, amps.shape[1] - lo))))


def set_amp_range(amps, start: int, vals: np.ndarray):
    """Overwrite amplitudes [start, start+m) with host values
    ``vals`` (2, m); returns the updated array in the SAME view/layout.
    Canonical states update tile-aligned whole blocks in one
    dynamic_update_slice plus read-modify-write edge tiles — never a
    full-state re-layout (the reference's setAmps writes into the local
    chunk in place, QuEST_cpu.c setAmps path)."""
    m = int(vals.shape[1])
    if m == 0:
        return amps
    orig_shape = amps.shape
    if amps.ndim != 4:
        if amps.shape[1] < BLK:
            return _set_flat(amps, jnp.asarray(vals, amps.dtype), start)
        amps = _as_canonical(amps)  # avoids int32 index overflow, see above
    end = start + m
    fb0 = (start + BLK - 1) >> BLK_BITS     # first fully-covered block
    fb1 = end >> BLK_BITS                   # one past the last full block
    if fb1 > fb0:
        off = (fb0 << BLK_BITS) - start
        blocks = np.ascontiguousarray(
            vals[:, off:off + ((fb1 - fb0) << BLK_BITS)]
        ).reshape(2, fb1 - fb0, DIM, DIM)
        amps = _set_blocks(amps, jnp.asarray(blocks, amps.dtype), fb0)
    edge_blocks = {start >> BLK_BITS, (end - 1) >> BLK_BITS} - set(
        range(fb0, fb1))
    for b in sorted(edge_blocks):
        blk = get_block_host(amps, b)
        lo = max(start, b << BLK_BITS)
        hi = min(end, (b + 1) << BLK_BITS)
        blk[:, lo - (b << BLK_BITS):hi - (b << BLK_BITS)] = (
            vals[:, lo - start:hi - start])
        amps = _set_blocks(
            amps, jnp.asarray(blk.reshape(2, 1, DIM, DIM), amps.dtype), b)
    return amps.reshape(orig_shape)
