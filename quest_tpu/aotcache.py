"""Persistent AOT executable cache (docs/design.md §31).

A first request that compiles pays seconds against a steady-state drain
of milliseconds — at serving scale interactive p99 is compile-bound, not
execution-bound.  This module eliminates the cold start by serializing compiled fusion
runners (``jax.experimental.serialize_executable``) to a content-hashed
on-disk cache keyed by the FULL semantic identity the plan layer
already computes, so a fresh process (or a fresh replica, or the
shrunk-mesh executor a failover restores onto) pays a millisecond
deserialize instead of a multi-second XLA compile.

Key schema (``runner_key``) — every knob that changes the compiled
artifact must appear here; anything missing is a silent wrong-answer
bug, anything extra is a silent cache miss:

  - toolchain: jax / jaxlib version + backend platform (a jax upgrade
    invalidates everything; ``_VERSION_OVERRIDE`` lets tests spoof it)
  - program identity: ``nloc`` + the planned program skeleton (which
    already folds the structure fingerprint, window split, megakernel
    grouping, permutation fast paths, and optimizer rewrite)
  - mesh identity: axis names/sizes, device kind, Topology.signature()
  - dispatch knobs: matmul precision, exchange-chunks key, batch mode,
    optimizer mode, QT_MEGAKERNEL planning flag, QT_PERM_FAST
  - argument signature: aval (shape, dtype, weak_type) of every operand

File format: ``b"QTAOT1\\n" + sha256(body) + body`` where body is a
pickle of ``{v, key, payload, in_tree, out_tree, meta}``.  Writes are
atomic (tempfile + os.replace in the cache dir); loads verify magic,
checksum, and key echo — any mismatch counts an error, records a
degradation, unlinks the bad entry, and falls back to a fresh compile
(bit-identical results either way; the cache is an accelerator, never
a correctness dependency).  Eviction is mtime-LRU against
``QT_AOT_CACHE_MAX_BYTES`` (default 1 GiB); hits ``os.utime`` the
entry so the hot set survives.

Enabled by ``QT_AOT_CACHE=<dir>``; with it unset ``wrap_runner``
returns the jitted runner untouched (zero overhead on the default
path).
"""

from __future__ import annotations

import hashlib
import os
import pickle
import tempfile
import threading
import time
from typing import Optional

import jax
import numpy as np

from . import telemetry as _telemetry

__all__ = [
    "enabled", "cache_dir", "max_bytes", "runner_key", "load", "store",
    "wrap_runner", "probe", "stats", "amps_struct", "arg_sig",
]

_DIR_ENV = "QT_AOT_CACHE"
_MAX_BYTES_ENV = "QT_AOT_CACHE_MAX_BYTES"
_DEFAULT_MAX_BYTES = 1 << 30  # 1 GiB
_MAGIC = b"QTAOT1\n"
_SUFFIX = ".aot"

# Spoofable toolchain tag: tests set _VERSION_OVERRIDE[0] to prove a
# jax upgrade invalidates every entry without actually upgrading jax.
_VERSION_OVERRIDE: list = [None]

_LOCK = threading.Lock()

# Keys whose executable is live in THIS process (wrapper dict or
# prewarm) — the explain predictor reports these as "memory": the next
# drain will not consult the disk tier at all.
_MEMORY_KEYS: set = set()

# Process-wide cache-tier accounting.  Deliberately a plain dict (the
# env._CACHE_STATS idiom) rather than telemetry counters: the AOT tier
# must account even with QT_TELEMETRY=off, and telemetry._series()
# folds these in so the consolidated block distinguishes the two cache
# tiers (ISSUE 20 satellite 6).
_STATS = {
    "hits": 0, "misses": 0, "puts": 0, "evictions": 0, "errors": 0,
    "bytes": 0, "saved_seconds": 0.0,
}


def reset_stats() -> None:
    """Test hook: zero the process-wide stats and the in-memory key set
    (simulates a fresh process for hit/miss pinning)."""
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0.0 if k == "saved_seconds" else 0
        _MEMORY_KEYS.clear()


def cache_dir() -> Optional[str]:
    d = os.environ.get(_DIR_ENV, "").strip()
    return d or None


def enabled() -> bool:
    return cache_dir() is not None


def max_bytes() -> int:
    try:
        return int(os.environ.get(_MAX_BYTES_ENV, str(_DEFAULT_MAX_BYTES)))
    except ValueError:
        return _DEFAULT_MAX_BYTES


def _version_tag() -> tuple:
    if _VERSION_OVERRIDE[0] is not None:
        return ("override", str(_VERSION_OVERRIDE[0]))
    try:
        import jaxlib

        jl = getattr(jaxlib, "__version__", "?")
    # qlint: allow(broad-except): jaxlib is an implementation detail of the jax install — any import/attr surprise degrades the tag component to "?" rather than disabling the cache
    except Exception:
        jl = "?"
    return (jax.__version__, jl, jax.default_backend())


def _mesh_tag(mesh) -> Optional[tuple]:
    """Portable mesh identity: axis layout + device kind + topology
    signature.  Deliberately NOT the Mesh object — a failover builds a
    fresh Mesh over the surviving devices, and the prewarmed shrunk-mesh
    entry must still hit."""
    if mesh is None:
        return None
    devs = np.asarray(mesh.devices).reshape(-1)
    try:
        kind = str(devs[0].device_kind)
    # qlint: allow(broad-except): device_kind is backend-dependent metadata — any failure degrades the key to "?" (still a valid, stable tag) instead of breaking dispatch
    except Exception:
        kind = "?"
    from .parallel import topology as _topo

    return (tuple(str(a) for a in mesh.axis_names),
            tuple(int(s) for s in np.asarray(mesh.devices).shape),
            kind, _topo.signature(int(devs.size)))


def _aval_of(x) -> tuple:
    """(shape, dtype, weak_type) signature of one runner operand —
    identical for a live concrete array, a numpy array, a Python float
    (weak-typed scalar), and the ShapeDtypeStruct a prewarm passes."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return (tuple(x.shape), str(x.dtype),
                bool(getattr(x, "weak_type", False)))
    aval = jax.core.get_aval(x)
    return (tuple(aval.shape), str(aval.dtype),
            bool(getattr(aval, "weak_type", False)))


def arg_sig(amps, arrays, probs) -> tuple:
    return ((_aval_of(amps),)
            + tuple(_aval_of(a) for a in arrays)
            + tuple(_aval_of(p) for p in probs))


def runner_key(nloc: int, program, mesh, precision, exchange_key,
               batch: int, sig: tuple) -> str:
    """sha256 hex over the full semantic identity of one compiled
    fusion runner (module docstring: the invalidation matrix)."""
    from . import circuit as _C
    from . import optimizer as _opt
    from .ops import fused as _fused

    parts = (
        "qt-aot-v1", _version_tag(), int(nloc), int(batch),
        str(precision), str(exchange_key), _mesh_tag(mesh),
        str(_opt.mode()), bool(_C.perm_fast_enabled()),
        bool(_fused.megakernel_planning()), repr(program), sig,
    )
    return hashlib.sha256(repr(parts).encode()).hexdigest()


def _path(key: str) -> str:
    return os.path.join(cache_dir(), key + _SUFFIX)


def _bump(name: str, by=1) -> None:
    with _LOCK:
        _STATS[name] += by


def _record_corrupt(path: str, why: str) -> None:
    _bump("errors")
    try:
        os.remove(path)
    except OSError:
        pass
    try:
        from . import resilience as _res

        _res.record_degradation(
            "aot_cache_corrupt",
            "AOT cache entry %s rejected (%s); fell back to a fresh "
            "compile — results are unaffected" % (
                os.path.basename(path), why))
    # qlint: allow(broad-except): recording the degradation is best-effort observability — the corruption fallback itself must complete even mid-teardown
    except Exception:
        pass
    _refresh_bytes()


def _scan() -> list:
    """[(path, size, mtime)] for every entry in the cache dir."""
    d = cache_dir()
    out = []
    if not d or not os.path.isdir(d):
        return out
    for name in os.listdir(d):
        if not name.endswith(_SUFFIX):
            continue
        p = os.path.join(d, name)
        try:
            st = os.stat(p)
        except OSError:
            continue
        out.append((p, st.st_size, st.st_mtime))
    return out

def _refresh_bytes() -> int:
    total = sum(sz for _p, sz, _m in _scan())
    with _LOCK:
        _STATS["bytes"] = total
    if _telemetry.enabled():
        _telemetry.set_gauge("aot_cache_bytes", float(total))
    return total


def _evict() -> None:
    """mtime-LRU eviction down to QT_AOT_CACHE_MAX_BYTES."""
    cap = max_bytes()
    entries = sorted(_scan(), key=lambda e: e[2])  # oldest first
    total = sum(sz for _p, sz, _m in entries)
    for p, sz, _m in entries:
        if total <= cap:
            break
        try:
            os.remove(p)
        except OSError:
            continue
        total -= sz
        _bump("evictions")
        if _telemetry.enabled():
            _telemetry.inc("aot_cache_evictions_total")
    with _LOCK:
        _STATS["bytes"] = total
    if _telemetry.enabled():
        _telemetry.set_gauge("aot_cache_bytes", float(total))


def load(key: str):
    """Consult the disk tier.  Returns (compiled, meta) on a verified
    hit, None on a miss; corruption of any flavour is a counted miss
    with a degradation record and the bad entry unlinked."""
    if not enabled():
        return None
    path = _path(key)
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except FileNotFoundError:
        _bump("misses")
        if _telemetry.enabled():
            _telemetry.inc("aot_cache_misses_total")
        return None
    except OSError as e:
        _record_corrupt(path, "unreadable: %s" % e)
        _bump("misses")
        if _telemetry.enabled():
            _telemetry.inc("aot_cache_misses_total")
        return None
    try:
        if blob[:len(_MAGIC)] != _MAGIC:
            raise ValueError("bad magic")
        off = len(_MAGIC)
        digest, body = blob[off:off + 32], blob[off + 32:]
        if hashlib.sha256(body).digest() != digest:
            raise ValueError("checksum mismatch")
        ent = pickle.loads(body)
        if ent.get("v") != 1 or ent.get("key") != key:
            raise ValueError("key/version mismatch")
        from jax.experimental.serialize_executable import (
            deserialize_and_load)

        compiled = deserialize_and_load(
            ent["payload"], ent["in_tree"], ent["out_tree"])
    # qlint: allow(broad-except): the corruption-safe fallback contract — a truncated/tampered/stale entry may fail anywhere in unpickle/deserialize, and every failure mode must degrade to a fresh compile
    except Exception as e:
        _record_corrupt(path, str(e) or type(e).__name__)
        _bump("misses")
        if _telemetry.enabled():
            _telemetry.inc("aot_cache_misses_total")
        return None
    meta = ent.get("meta") or {}
    saved = float(meta.get("compile_seconds", 0.0))
    _bump("hits")
    _bump("saved_seconds", saved)
    if _telemetry.enabled():
        _telemetry.inc("aot_cache_hits_total")
        if saved:
            _telemetry.inc("aot_compile_seconds_saved_total", saved)
    try:
        os.utime(path)  # refresh LRU position
    except OSError:
        pass
    return compiled, meta


def store(key: str, compiled, compile_seconds: float, meta=None) -> bool:
    """Persist one compiled executable (atomic tempfile + os.replace),
    then evict down to the byte cap.  Best-effort: any failure counts
    an error and the caller keeps its in-memory executable."""
    d = cache_dir()
    if d is None:
        return False
    try:
        from jax.experimental.serialize_executable import serialize

        payload, in_tree, out_tree = serialize(compiled)
        ent = {
            "v": 1, "key": key, "payload": payload,
            "in_tree": in_tree, "out_tree": out_tree,
            "meta": dict(meta or {},
                         compile_seconds=float(compile_seconds),
                         version=_version_tag()),
        }
        body = pickle.dumps(ent, protocol=pickle.HIGHEST_PROTOCOL)
        blob = _MAGIC + hashlib.sha256(body).digest() + body
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, _path(key))
        except BaseException:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise
    # qlint: allow(broad-except): persistence is an accelerator, never a dependency — serialize-unsupported backends, full disks, and permission errors all leave the caller its in-memory executable
    except Exception:
        _bump("errors")
        return False
    _bump("puts")
    if _telemetry.enabled():
        _telemetry.inc("aot_cache_puts_total")
    _evict()
    return True


def stats() -> dict:
    with _LOCK:
        out = dict(_STATS)
    out["enabled"] = enabled()
    out["dir"] = cache_dir()
    out["memory_keys"] = len(_MEMORY_KEYS)
    return out


def amps_struct(num_amps: int, batch: int, dtype, mesh):
    """ShapeDtypeStruct standing in for a register's ``_amps`` operand —
    the SAME aval (shape, dtype, sharding) a live drain dispatches, so
    a prewarm from analytic shapes produces the key and executable the
    live request then hits."""
    if batch:
        shape = (batch, 2, num_amps)
    else:
        from .qureg import device_amps_shape

        nsh = 0
        if mesh is not None and num_amps >= mesh.devices.size:
            from .parallel import dist as PAR

            nsh = PAR.num_shard_bits(mesh)
        shape = device_amps_shape(num_amps.bit_length() - 1, nsh)
    sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from .env import AMP_AXIS

        spec = P(None, None, AMP_AXIS) if batch else P(None, AMP_AXIS)
        sharding = NamedSharding(mesh, spec)
    return jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=sharding)


def probe(nloc: int, program, mesh, precision, exchange_key, batch: int,
          sig: tuple) -> dict:
    """Side-effect-free hit/miss prediction for explainCircuit: computes
    the key the next drain would use and reports where its executable
    currently lives.  ``memory`` = a live in-process executable (the
    disk tier will not be consulted); ``hit`` / ``miss`` = the disk
    tier's answer for a fresh executor."""
    if not enabled():
        return {"enabled": False, "status": "disabled", "key": None}
    if not program:
        return {"enabled": True, "status": "uncacheable", "key": None}
    key = runner_key(nloc, program, mesh, precision, exchange_key,
                     batch, sig)
    with _LOCK:
        in_mem = key in _MEMORY_KEYS
    if in_mem:
        status = "memory"
    elif os.path.exists(_path(key)):
        status = "hit"
    else:
        status = "miss"
    return {"enabled": True, "status": status, "key": key}


def wrap_runner(run, *, nloc: int, program, mesh, precision,
                exchange_key, batch: int):
    """Wrap one freshly-traced fusion runner with the AOT tier.

    Disabled (no QT_AOT_CACHE): returns ``run`` untouched.  Enabled:
    returns a drop-in callable that, per argument signature,
    consults-before-compile (disk hit -> deserialize) and
    persists-on-miss (``run.lower(...).compile()`` timed + stored),
    then dispatches the compiled executable directly.  Tracer operands
    (a drain reached from inside a user jit) fall through to the plain
    jit, as does ANY failure in the cache path before execution —
    the cache never gates correctness.

    The wrapper carries a ``.prewarm(amps_spec, arrays, probs)``
    attribute: load-or-compile from analytic ShapeDtypeStructs WITHOUT
    executing — the serve-layer warm pool's entry point.  A
    threading.Lock serializes the prewarmer thread against the live
    scheduler so a racing first request cannot double-compile."""
    if not enabled():
        return run

    compiled_by_sig: dict = {}
    lock = threading.Lock()
    first = [True]

    def _materialize(sig, args):
        """Disk-load or fresh-compile the executable for ``sig``.
        Returns (compiled, from_cache); caller holds ``lock``."""
        key = runner_key(nloc, program, mesh, precision, exchange_key,
                         batch, sig)
        got = load(key)
        if got is not None:
            compiled = got[0]
            from_cache = True
        else:
            t0 = time.perf_counter()
            compiled = run.lower(*args).compile()
            store(key, compiled, time.perf_counter() - t0)
            from_cache = False
        compiled_by_sig[sig] = compiled
        with _LOCK:
            _MEMORY_KEYS.add(key)
        return compiled, from_cache

    def wrapped(amps, arrays, probs):
        if isinstance(amps, jax.core.Tracer):
            return run(amps, arrays, probs)
        t0 = time.perf_counter()
        try:
            sig = arg_sig(amps, arrays, probs)
            with lock:
                compiled = compiled_by_sig.get(sig)
                if compiled is None:
                    compiled, from_cache = _materialize(
                        sig, (amps, arrays, probs))
                else:
                    from_cache = True  # warm: memory tier (or prewarm)
        # qlint: allow(broad-except): any cache-path failure BEFORE execution falls back to the plain jit — the donated operand is untouched, results identical
        except Exception:
            return run(amps, arrays, probs)
        out = compiled(amps, arrays, probs)
        if first[0]:
            first[0] = False
            if _telemetry.enabled():
                jax.block_until_ready(out)
                _telemetry.observe(
                    "first_request_seconds", time.perf_counter() - t0,
                    fingerprint_cached="true" if from_cache else "false")
        return out

    def prewarm(amps_spec, arrays, probs):
        """Load-or-compile without executing.  Returns ``"present"``
        (already live), ``"hit"`` (deserialized from disk), or
        ``"compiled"`` (fresh AOT compile, persisted)."""
        sig = arg_sig(amps_spec, arrays, probs)
        with lock:
            if sig in compiled_by_sig:
                return "present"
            _c, from_cache = _materialize(sig, (amps_spec, arrays, probs))
        return "hit" if from_cache else "compiled"

    wrapped.prewarm = prewarm
    wrapped.aot_wrapped = True
    return wrapped
