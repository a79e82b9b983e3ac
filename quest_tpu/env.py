"""Execution environment: device mesh discovery and seeding.

TPU-native analogue of the reference's ``QuESTEnv`` (QuEST.h:361, {rank,
numRanks}) and ``createQuESTEnv`` (MPI_Init + rank discovery,
QuEST_cpu_distributed.c:129-160; GPU probe, QuEST_gpu.cu:446-478).  Instead
of MPI ranks, the environment owns a 1-D ``jax.sharding.Mesh`` over the
amplitude axis; a Qureg's amplitudes are sharded over it by their leading
(most-significant-qubit) index bits — exactly the reference's chunk scheme
(QuEST.h:330-338) expressed as a NamedSharding.  Multi-host TPU slices join
the same mesh via ``jax.distributed`` (the analogue of MPI_Init), and the
collectives ride ICI/DCN instead of MPI.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import rng

AMP_AXIS = "amps"

# Every module imports shard_map from HERE so the whole package tracks one
# spelling of its options.
from jax import shard_map as _shard_map_impl  # noqa: E402


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: Optional[bool] = None):
    """jax.shard_map with ``check_vma`` forwarded only when given
    (omitted -> the jax default)."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return _shard_map_impl(f, **kwargs)


@dataclasses.dataclass
class QuESTEnv:
    """Holds the device mesh. ``rank``/``num_ranks`` kept for reference-API
    parity: rank = jax.process_index(), num_ranks = number of mesh devices."""

    mesh: Mesh
    rank: int
    num_ranks: int
    seeds: tuple
    # hierarchical hosts x chips arrangement of the amplitude mesh
    # (parallel/topology.py; resolved from QT_TOPOLOGY at creation and
    # carried through shrink_env so a failed-over env keeps classifying
    # its surviving interconnect correctly even while the env var still
    # describes the old shape).  None only on hand-built envs; accessors
    # fall back to the flat single-host arrangement.
    topology: Optional[object] = None

    @property
    def num_devices(self) -> int:
        return int(np.prod(self.mesh.devices.shape))

    def amp_sharding(self) -> NamedSharding:
        """For SoA state arrays (2, num_amps): shard the amplitude axis."""
        return NamedSharding(self.mesh, PartitionSpec(None, AMP_AXIS))

    def vec_sharding(self) -> NamedSharding:
        """For flat per-amplitude vectors (e.g. DiagonalOp channels)."""
        return NamedSharding(self.mesh, PartitionSpec(AMP_AXIS))

    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    def sharding_for_dim(self, dim: int) -> NamedSharding:
        """Per-amplitude vector sharding when the vector spans the mesh,
        replicated otherwise (small registers replicate rather than being
        rejected — see validation.validate_num_qubits)."""
        return (self.vec_sharding() if dim >= self.num_devices
                else self.replicated_sharding())


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join a multi-host run — the analogue of the reference's ``MPI_Init``
    (QuEST_cpu_distributed.c:129-160).  Call once per host BEFORE
    ``create_quest_env``; afterwards ``jax.devices()`` spans every host and
    the amplitude mesh covers the whole slice (collectives ride ICI within
    a slice and DCN across slices).  On TPU pods all arguments are
    auto-detected from the environment."""
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


_CACHE_WIRED = [False]

# persistent-cache observability: hit/miss counts from jax.monitoring
# events, reported by getEnvironmentString — a long-lived serving process
# can tell whether its restarts are actually warm (bench_r05 measured up
# to 7.7 s compile_s per bench config, re-paid on every cold start)
_CACHE_STATS = {"hits": 0, "misses": 0, "dir": None}
_CACHE_LISTENERS = [False]


def _register_cache_listeners() -> None:
    if _CACHE_LISTENERS[0]:
        return
    _CACHE_LISTENERS[0] = True
    try:  # pragma: no cover - monitoring API is version-dependent
        import jax.monitoring as _mon

        def _on_event(event: str, **kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                _CACHE_STATS["hits"] += 1

        def _on_duration(event: str, duration: float, **kw) -> None:
            if event == "/jax/compilation_cache/cache_misses":
                _CACHE_STATS["misses"] += 1

        _mon.register_event_listener(_on_event)
        _mon.register_event_duration_secs_listener(_on_duration)
    except (ImportError, AttributeError):
        pass


def compile_cache_stats() -> dict:
    """{'hits': int, 'misses': int, 'dir': str | None} for the persistent
    compilation cache this process is using (dir None = not wired)."""
    return dict(_CACHE_STATS)


def _checkout_cache_dir() -> str:
    """``<checkout>/.jax_cache``: the directory beside this package — a
    fixed path, since the path is part of every cache key."""
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")


def _enable_compilation_cache() -> None:
    """Persistent XLA compilation cache.  Where JAX_COMPILATION_CACHE_DIR
    is set (or jax_compilation_cache_dir configured before
    createQuESTEnv), JAX uses that directory and nothing else is set
    here.  Otherwise QT_COMPILE_CACHE=<dir> (alias QT_COMPILE_CACHE_DIR)
    names one on any backend, and an accelerator backend defaults to
    ``<checkout>/.jax_cache`` (_checkout_cache_dir); the CPU backend
    stays uncached by default, because CPU executables embed the compile
    host's microarchitecture.  QT_NO_COMPILE_CACHE=1 opts out.  Hits and
    misses are counted (jax.monitoring listeners) and surfaced by
    getEnvironmentString."""
    if _CACHE_WIRED[0] or os.environ.get("QT_NO_COMPILE_CACHE") == "1":
        return
    _CACHE_WIRED[0] = True
    user_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or jax.config.jax_compilation_cache_dir)
    if user_dir:
        _CACHE_STATS["dir"] = user_dir
        _register_cache_listeners()
        return
    cache_dir = (os.environ.get("QT_COMPILE_CACHE")
                 or os.environ.get("QT_COMPILE_CACHE_DIR"))
    if cache_dir is None:
        if jax.default_backend() == "cpu":
            return
        cache_dir = _checkout_cache_dir()
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError:  # read-only checkout: compile uncached
        return
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every compiled program: the per-pass kernels each compile
    # in a second or two, under the default thresholds
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _CACHE_STATS["dir"] = cache_dir
    _register_cache_listeners()


def create_quest_env(
    devices: Optional[Sequence[jax.Device]] = None,
    num_devices: Optional[int] = None,
) -> QuESTEnv:
    """createQuESTEnv (QuEST.h:1851).

    Uses all visible devices by default, truncated to the largest power of
    two — the reference enforces power-of-2 ranks (validateNumRanks,
    QuEST_validation.c:331-343) because amplitude chunks split on index bits;
    the same constraint holds for the mesh.  Also wires the persistent
    XLA compilation cache (see _enable_compilation_cache).
    """
    _enable_compilation_cache()
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    n = len(devices)
    pow2 = 1 << (n.bit_length() - 1)
    devices = devices[:pow2]
    mesh = Mesh(np.array(devices), (AMP_AXIS,))
    from .parallel import topology as _topo

    env = QuESTEnv(
        mesh=mesh,
        rank=jax.process_index(),
        num_ranks=pow2,
        seeds=(),
        topology=_topo.resolve(pow2),
    )
    seed_quest_default(env)
    return env


def shrink_env(env: QuESTEnv, num_devices: int, *,
               exclude_index: Optional[int] = None,
               exclude_indices: Optional[Sequence[int]] = None) -> QuESTEnv:
    """A degraded environment over a power-of-two subset of ``env``'s
    devices — the mesh half of the elastic failover path
    (resilience._failover) and of loadQureg's auto-reshard.

    ``exclude_index`` drops one device (the presumed-dead shard) before
    truncating; ``exclude_indices`` drops a set — the host-loss path
    excludes the dead host's whole device range
    (topology.host_range) so the surviving mesh is built from intact
    hosts only.  The result keeps ``env``'s seeds WITHOUT reseeding —
    the RNG streams belong to the run, not the mesh, and a failover
    restores them from the checkpoint anyway.  The degraded topology is
    derived with topology.shrink: a whole-host loss keeps the
    chips-per-host arrangement (2x4 -> 1x4), a sub-host shrink
    collapses to single-host."""
    dead = set() if exclude_indices is None else {
        int(i) for i in exclude_indices}
    if exclude_index is not None:
        dead.add(int(exclude_index))
    devs = [d for i, d in enumerate(env.mesh.devices.reshape(-1).tolist())
            if i not in dead]
    num_devices = int(num_devices)
    if num_devices < 1 or num_devices & (num_devices - 1):
        raise ValueError(
            f"shrink_env: num_devices must be a positive power of two, "
            f"got {num_devices}")
    if num_devices > len(devs):
        raise ValueError(
            f"shrink_env: asked for {num_devices} devices but only "
            f"{len(devs)} survive in this environment")
    mesh = Mesh(np.array(devs[:num_devices]), (AMP_AXIS,))
    from .parallel import topology as _topo

    return QuESTEnv(mesh=mesh, rank=env.rank, num_ranks=num_devices,
                    seeds=env.seeds,
                    topology=_topo.shrink(env.topology, num_devices))


def destroy_quest_env(env: QuESTEnv) -> None:
    """destroyQuESTEnv (QuEST.h:1864) — nothing to free; arrays are GC'd."""


def sync_quest_env(env: QuESTEnv) -> None:
    """syncQuESTEnv (QuEST.h:1875): the reference issues an MPI_Barrier /
    cudaDeviceSynchronize.  XLA program order makes a barrier unnecessary;
    we block on outstanding async dispatches for timing parity."""
    (jax.device_put(0) + 0).block_until_ready()


def sync_quest_success(success_code: int = 1) -> int:
    """syncQuESTSuccess (QuEST_cpu_distributed.c:166-170) AND-reduces a flag
    across ranks; single-process JAX returns it unchanged."""
    return int(success_code)


def report_quest_env(env: QuESTEnv) -> None:
    """Print execution-environment parameters (QuEST.h:1893)."""
    print(get_environment_string(env))


def get_environment_string(env: QuESTEnv) -> str:
    """getEnvironmentString (QuEST.h:1912) — reference format:
    'CUDA=.. OpenMP=.. MPI=.. threads=.. ranks=..'; ours reports the mesh,
    plus any recorded graceful degradations (e.g. a Pallas kernel that
    failed to lower and fell back to the XLA path — resilience.py)."""
    backend = jax.default_backend()
    s = (
        f"EnvType=quest_tpu Backend={backend} Devices={env.num_devices} "
        f"MeshAxes={AMP_AXIS} Processes={jax.process_count()}"
    )
    from . import resilience
    from .parallel import dist
    from .parallel import topology as _topo

    t = env.topology if env.topology is not None \
        else _topo.resolve(env.num_devices)
    s += f" Topology={t.describe()}"
    s += f" ExchangeChunks={dist.exchange_config_key() or 'auto'}"
    # reproducibility surface: when the measurement RNG is still on its
    # time+pid default seed, report the chosen keys so the run can be
    # replayed exactly with seedQuEST(env, <keys>) (rng.py contract)
    if getattr(rng.GLOBAL_RNG, "default_seeded", False):
        s += " DefaultSeed=" + ",".join(str(k) for k in rng.GLOBAL_RNG._keys)
    cache = compile_cache_stats()
    if cache["dir"]:
        s += (f" CompileCache={cache['dir']}"
              f"(hits={cache['hits']} misses={cache['misses']})")
    # §31 persistent AOT executable tier — a distinct line from the XLA
    # compile cache above: AotCache hits skip compilation ACROSS
    # processes (deserialize), CompileCache hits dedup within one.
    # Lazy import: env(rank 5) may not import dist-stratum modules at
    # module level (analysis/rules_layering.py)
    from . import aotcache as _aotcache

    if _aotcache.enabled():
        aot = _aotcache.stats()
        s += (f" AotCache={aot['dir']}"
              f"(hits={aot['hits']} misses={aot['misses']} "
              f"puts={aot['puts']} bytes={aot['bytes']})")
    degraded = resilience.degradation_report()
    if degraded:
        s += " Degraded=[" + "; ".join(
            f"{k}: {v}" for k, v in sorted(degraded.items())) + "]"
    # consolidated observability block (telemetry.py absorbs the cache
    # counters and degradation registry above as series of the same
    # namespace; the legacy fields stay for compatibility)
    from . import telemetry

    # elastic-recovery surface: completed failovers and guarded-collective
    # timeouts, pulled from the registry so operators see degraded-mesh
    # history without parsing the telemetry block
    failovers = telemetry.counter_total("failovers_total")
    if failovers:
        s += f" Failovers={int(failovers)}"
    timeouts = telemetry.counter_total("exchange_timeouts_total")
    if timeouts:
        s += f" ExchangeTimeouts={int(timeouts)}"
    # serving-resilience surface (serve.py, docs/design.md §27): retry /
    # quarantine / failover+heal history and the live degraded flag
    s_retr = telemetry.counter_total("serve_bank_retries_total")
    s_quar = telemetry.counter_total("serve_jobs_quarantined_total")
    s_fail = telemetry.counter_total("serve_failovers_total")
    s_heal = telemetry.counter_total("serve_heals_total")
    s_deg = telemetry.gauge_max("serve_degraded")
    if s_retr or s_quar or s_fail or s_heal or s_deg:
        s += (f" Serve=retries:{int(s_retr)},"
              f"quarantined:{int(s_quar)},failovers:{int(s_fail)},"
              f"heals:{int(s_heal)},degraded:{int(s_deg or 0)}")
    # peak HBM watermark over devices (hbm_watermark_bytes gauge, sampled
    # by the fusion drain at window boundaries — utils/profiling.py)
    peak = telemetry.gauge_max("hbm_watermark_bytes")
    if peak is not None:
        s += f" HbmPeak={int(peak)}"
    # memory-governor surface: policy + budget when active, plus any
    # spill / OOM-retry history (governor.py; degradations above carry
    # the per-rung reasons)
    from . import governor

    if governor.enabled():
        s += (f" MemGovernor={governor.policy()}"
              f"(budget={governor.budget_bytes()}"
              f" resident={governor.resident_bytes()})")
    # circuit-optimizer surface (optimizer.py): active mode plus
    # cumulative rewrite work when any has been recorded
    from . import optimizer

    s += f" {optimizer.summary_line()}"
    # §28 permutation fast paths (QT_PERM_FAST): flagged when disabled,
    # plus cumulative per-route history once any gate lowered this way
    from . import circuit as _circuit

    pf = _circuit.perm_fast_enabled()
    pg = telemetry.counter_total("permutation_gates_total")
    if not pf or pg:
        s += f" PermFast={'on' if pf else 'off'}"
        routes = ",".join(
            f"{r}:{int(telemetry.counter_sum('permutation_gates_total', route=r))}"
            for r in ("relabel", "gather", "exchange")
            if telemetry.counter_sum("permutation_gates_total", route=r))
        if routes:
            s += f"({routes})"
    # §29 window megakernel (QT_MEGAKERNEL): mode plus the planning
    # verdict in parentheses, and cumulative per-route dispatch history
    # once any fused window executed through either arm
    from .ops import fused as _fused

    mk = _fused.megakernel_mode()
    mk_total = telemetry.counter_total("megakernel_dispatch_total")
    if mk == "on" or mk_total:
        s += (f" Megakernel={mk}"
              f"({'on' if _fused.megakernel_planning() else 'off'})")
        mk_routes = ",".join(
            f"{r}:{int(telemetry.counter_sum('megakernel_dispatch_total', route=r))}"
            for r in ("mega", "fallback")
            if telemetry.counter_sum("megakernel_dispatch_total", route=r))
        if mk_routes:
            s += f"[{mk_routes}]"
    spills = telemetry.counter_total("spills_total")
    if spills:
        s += f" Spills={int(spills)}"
    ooms = telemetry.counter_total("oom_retries_total")
    if ooms:
        s += f" OomRetries={int(ooms)}"
    s += f" [telemetry: {telemetry.summary()}]"
    return s


def seed_quest(env: QuESTEnv, seeds: Sequence[int]) -> None:
    """seedQuEST (QuEST.h:3341): seeds the measurement RNG identically on
    every process (reference broadcasts the key,
    QuEST_cpu_distributed.c:1384-1395; with jax.distributed every process
    already passes the same seeds)."""
    env.seeds = tuple(int(s) for s in seeds)
    rng.GLOBAL_RNG.seed(env.seeds)
    from .ops import measurement

    measurement.KEYS.seed(env.seeds)


def seed_quest_default(env: QuESTEnv) -> None:
    """seedQuESTDefault (QuEST.h:3324): time+pid key."""
    rng.GLOBAL_RNG.seed_default()
    env.seeds = tuple(rng.GLOBAL_RNG._keys)
    from .ops import measurement

    measurement.KEYS.seed(env.seeds)
