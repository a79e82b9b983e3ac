"""Compile the main path's kernels for a described TPU v5e, at real size.

Nothing here runs on a chip: the TPU compiler installed with jaxlib
compiles for a v5e topology that is described, not attached, and refuses
what the chip would refuse — a program that does not fit its 16 GiB HBM,
a Mosaic kernel it cannot lower, a kernel over its scoped VMEM.  Every
kernel is compiled on the input the register holds: the canonical
(2, 2^(n-14), 128, 128) device shape (qureg.device_amps_shape), with
Pallas interpret mode off (the module fixture steers the backend checks
that would otherwise see this process's CPU).

A 30-qubit f32 state is 8 GiB: a kernel that writes a second state does
not fit one chip, so each case checks that the compiled program runs in
place (no state-sized temporary; the donated state aliases the output).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import chip_smoke
import quest_tpu as qt
from quest_tpu import circuit as CIRC
from quest_tpu import fusion, governor
from quest_tpu.env import AMP_AXIS, QuESTEnv
from quest_tpu.ops import bigstate
from quest_tpu.ops import calculations as CALC
from quest_tpu.ops import element as E
from quest_tpu.ops import fused
from quest_tpu.ops import kernels as K
from quest_tpu.ops import paulis as PAULI
from quest_tpu.parallel import dist as PAR
from quest_tpu.parallel import topology as TOPO
from quest_tpu.qureg import Qureg, device_amps_shape

N = 30
STATE_BYTES = 2 * (1 << N) * 4
# a 32-qubit state over four chips: one 8 GiB shard each
N32 = 32
SHARD32_BYTES = 2 * (1 << (N32 - 2)) * 4
# the HBM a v5e program may use, as its compiler reports it ("15.75G")
V5E_HBM_BYTES = int(15.75 * (1 << 30))
# temporaries a kernel may hold beside the state: tables, partial sums
SMALL = 64 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def tpu_build(topo):
    """Steer the backend checks to the TPU's branch for this module (the
    process's own backend is the CPU), in f32 with 32-bit indices as on
    the chip (the suite runs with x64 on; Mosaic index maps must return
    i32), with the persistent compile cache off (a TPU entry cannot be
    read back without a chip)."""
    mp = pytest.MonkeyPatch()
    mp.setattr(fused, "_interpret_default", lambda: False)
    mp.setattr(governor, "_device_limit_bytes", lambda: V5E_HBM_BYTES)
    cache_on = jax.config.jax_enable_compilation_cache
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    qt.set_precision(1)
    yield
    qt.set_precision(2)
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", cache_on)
    mp.undo()
    fusion._plan_runner.cache_clear()
    fusion._plan_cache.clear()
    jax.clear_caches()


@pytest.fixture(scope="module")
def one_chip(topo, tpu_build):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo, tpu_build):
    return Mesh(np.array(topo.devices), (AMP_AXIS,))


def _state(sharding, n=N, shard_bits=0):
    return jax.ShapeDtypeStruct(device_amps_shape(n, shard_bits),
                                jnp.float32, sharding=sharding)


def _in_place(compiled, state_bytes=STATE_BYTES):
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < SMALL, ma.temp_size_in_bytes
    assert ma.alias_size_in_bytes == state_bytes, ma.alias_size_in_bytes
    return ma


@pytest.fixture(scope="module")
def drain30(one_chip):
    """chip_smoke's phase-1 circuit at 30 qubits, planned by the fusion
    drain for one v5e (no state is allocated) and compiled."""
    env = qt.createQuESTEnv(num_devices=1)
    q = Qureg(N, env, is_density_matrix=False)
    fusion.start_gate_fusion(q)
    chip_smoke.apply_circuit(qt, q, chip_smoke.random_circuit(N))
    program, arrays, _fp, nloc, nsh = fusion.plan_items_quiet(
        q, list(q._fusion.gates))
    runner = fusion._plan_runner(nloc, program, None, "highest", None, 0)
    arr = tuple(jax.ShapeDtypeStruct(np.shape(a), jnp.float32,
                                     sharding=one_chip) for a in arrays)
    compiled = runner.lower(_state(one_chip), arr, ()).compile()
    pred = governor.predict_drain(q, program, arrays, nloc=nloc, nsh=nsh)
    return program, arrays, compiled, pred


def test_window_pass_in_place_30q(one_chip):
    mats = jax.ShapeDtypeStruct((1, 2, 128, 128), jnp.float32,
                                sharding=one_chip)

    def f(x, a, b):
        return fused._apply_window_stack_jit(x, a, b, num_qubits=N, k=10,
                                             interpret=False)

    _in_place(jax.jit(f, donate_argnums=0).lower(
        _state(one_chip), mats, mats).compile())


def _mats(sharding, rank=1):
    return jax.ShapeDtypeStruct((rank, 2, 128, 128), jnp.float32,
                                sharding=sharding)


# (num_qubits, rank, k, apply_a, apply_b, with_mask) of window passes:
# every rank-1 pass the 30-qubit benchmark plans issue (the random-layer
# drain's dual-side, B-only, A-only and mask-only passes, masked or not,
# from the 4-d k = 7 view to k = 23, where one block spans the whole hi
# axis; the QFT's folded low layers and bit reversal), then rank 2 and 4
# (crossing controlled rotations; SWAPs and general two-qubit gates) in
# each view: the canonical 4-d block (k = 7) and the 5-d view (k = 10,
# 23) at 30 qubits, and ranks 1-4 in the 4-d view of k = 8, 9 (mid 2, 4)
# at 28, where that view's retile copy of the state fits beside it
_SIDES = ((True, True), (False, True), (True, False))
WINDOW_CASES = (
    [(N, 1) + sig for sig in sorted(
        {(k, True, True, False) for k in (7, 10, 12, 14, 16, 18, 22, 23)}
        | {(k, True, True, True) for k in (7, 23)}
        | {(k, False, True, False)
           for k in (7, 10, 11, 12, 14, 16, 17, 19, 21, 22, 23)}
        | {(k, False, True, True) for k in (7, 19, 20)}
        | {(18, True, False, True)}
        | {(k, False, False, True) for k in (7, 17, 23)})]
    + [(N, rank, k, a, b, m) for rank in (2, 4) for k in (7, 10, 23)
       for (a, b) in _SIDES for m in (False, True)]
    + [(28, rank, k, a, b, m) for rank in (1, 2, 4) for k in (8, 9)
       for (a, b) in _SIDES for m in (False, True)])


@pytest.mark.parametrize("n,rank,k,apply_a,apply_b,with_mask",
                         WINDOW_CASES)
def test_window_pass_signatures_fit(one_chip, n, rank, k, apply_a, apply_b,
                                    with_mask):
    """Each pass compiles in its three-product form within the scoped
    VMEM at the block size _apply_window_stack_jit picks for it and
    writes the state in place.  A rank 3-4 dual-side pass takes 4
    blocks, so at k > 7 it runs on the 4-d view, and at 30 qubits that
    view's retile copy of the 8 GiB state does not fit the HBM."""
    state_bytes = 2 * (1 << n) * 4
    m = _mats(one_chip, rank)
    mask = jax.ShapeDtypeStruct((2, 128, 128), jnp.float32,
                                sharding=one_chip)

    def f(x, a, b, *mk):
        return fused._apply_window_stack_jit(
            x, a, b, *mk, num_qubits=n, k=k, apply_a=apply_a,
            apply_b=apply_b, interpret=False)

    args = (m, m, mask) if with_mask else (m, m)
    lowered = jax.jit(f, donate_argnums=0).lower(_state(one_chip, n), *args)
    if n == N and rank > 2 and apply_a and apply_b and k > 7:
        with pytest.raises(Exception, match="hbm"):
            lowered.compile()
        return
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    if k in (8, 9):
        ma = compiled.memory_analysis()
        assert ma.temp_size_in_bytes < state_bytes + SMALL
        assert ma.alias_size_in_bytes == state_bytes
    else:
        _in_place(compiled, state_bytes)


# megawin groups at the row caps megawin_row_cap gives each rank: rank 2
# on 8 rows (k = 10) single- and dual-side, masked or not; groups that
# open with a k = 7 member; rank 4 on 4 rows (k = 9)
MEGAWIN_SPECS_30Q = (
    ((10, 2, False, True, False),),
    ((10, 2, False, True, True),),
    ((10, 2, True, False, True),),
    ((10, 2, True, True, True),),
    ((7, 2, False, True, False), (10, 2, False, True, False)),
    ((7, 1, True, True, True), (8, 1, False, True, True),
     (10, 1, True, True, True)),
    ((9, 4, True, True, True),),
    ((9, 4, False, True, True),),
)


@pytest.mark.parametrize("spec", MEGAWIN_SPECS_30Q)
def test_megawin_groups_in_place_30q(one_chip, spec):
    """A megawin group compiles within the scoped VMEM and sweeps the
    8 GiB state in place."""
    ops = []
    for (_k, rank, _a, _b, with_mask) in spec:
        ops += [_mats(one_chip, rank)] * 2
        if with_mask:
            ops.append(jax.ShapeDtypeStruct((2, 128, 128), jnp.float32,
                                            sharding=one_chip))

    def f(x, *ops):
        return fused._apply_megawin_jit(x, *ops, num_qubits=N, spec=spec,
                                        interpret=False)

    compiled = jax.jit(f, donate_argnums=0).lower(
        _state(one_chip), *ops).compile()
    _in_place(compiled)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind", ["fused", "swapfused", "sigma_swap"])
def test_other_in_place_passes_30q(one_chip, kind):
    """The remaining plan ops the governor prices at no extra state
    (governor.IN_PLACE_OPS): the cluster pass, the segment-swap cluster
    pass and the QFT's sigma swap."""
    m = _mats(one_chip)
    if kind == "fused":
        def f(x, a, b):
            return fused._apply_cluster_stack_jit(x, a, b, num_qubits=N,
                                                  interpret=False)
        args = (m, m)
    elif kind == "swapfused":
        def f(x, a, b):
            return fused._apply_swap_cluster_stack_jit(
                x, a, b, num_qubits=N, h=20, b=7, m=fused.MAX_FUSED_SWAP_M,
                interpret=False)
        args = (m, m)
    else:
        ctab, dtab = bigstate.sigma_pair_tables(7)
        args = tuple(jax.ShapeDtypeStruct(np.shape(t), jnp.int32,
                                          sharding=one_chip)
                     for t in (ctab, dtab))

        def f(x, c, d):
            return bigstate._sigma_swap_jit(x, c, d, num_qubits=N,
                                            group_bits=7, interpret=False)
    compiled = jax.jit(f, donate_argnums=0).lower(
        _state(one_chip), *args).compile()
    _in_place(compiled)
    assert "tpu_custom_call" in compiled.as_text()
    assert kind in governor.IN_PLACE_OPS


def test_drain_runner_in_place_30q(drain30):
    program, arrays, compiled, _pred = drain30
    ma = _in_place(compiled)
    pass_bytes = sum(int(np.asarray(a).nbytes) for a in arrays)
    assert ma.temp_size_in_bytes <= pass_bytes
    assert "tpu_custom_call" in compiled.as_text()


def test_drain_plan_is_in_place_ops_only_30q(drain30):
    """The planner keeps the long-range CNOTs' diagonal halves as the
    in-place diagonal pass and the permutation gates in window passes
    (a second state does not fit): no out-of-place op is planned."""
    program = drain30[0]
    kinds = {sk[0] for part in program if part[0] == "plan"
             for sk in part[1]}
    assert {p[0] for p in program} == {"plan"}
    assert kinds <= governor.IN_PLACE_OPS, kinds
    assert "diag" in kinds


def test_governor_prediction_matches_compiler_30q(drain30):
    _program, _arrays, compiled, pred = drain30
    ma = compiled.memory_analysis()
    actual = (ma.argument_size_in_bytes + ma.output_size_in_bytes
              - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert abs(pred["predicted_peak_bytes"] - actual) <= 0.1 * actual
    assert pred["predicted_peak_bytes"] <= V5E_HBM_BYTES


def test_full_qft_in_place_30q(one_chip):
    def f(x):
        return CIRC.fused_qft(x, N, 0, N)

    compiled = jax.jit(f, donate_argnums=0).lower(_state(one_chip)).compile()
    _in_place(compiled)
    assert "tpu_custom_call" in compiled.as_text()


def test_pauli_expectation_kernel_30q(one_chip):
    terms = 4
    codes = jax.ShapeDtypeStruct((terms, N), jnp.int32, sharding=one_chip)
    coeffs = jax.ShapeDtypeStruct((terms,), jnp.float32, sharding=one_chip)
    compiled = PAULI.expec_pauli_sum_scan.lower(
        _state(one_chip), codes, coeffs, num_qubits=N).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < SMALL
    assert "tpu_custom_call" in compiled.as_text()


def test_pauli_rotation_kernel_29q(one_chip):
    """The direct-rotation kernel reads each block's XOR partner while
    writing, so it writes a second state: it fits at 29 qubits, not at
    30 (applyTrotterCircuit at 30q is listed under ROADMAP Reach 1)."""
    n, terms = 29, 4
    codes = jax.ShapeDtypeStruct((terms, n), jnp.int32, sharding=one_chip)
    angles = jax.ShapeDtypeStruct((terms,), jnp.float32, sharding=one_chip)
    compiled = PAULI.trotter_scan.lower(
        _state(one_chip, n), codes, angles, num_qubits=n,
        rep_qubits=n).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes <= STATE_BYTES // 2 + SMALL
    assert "tpu_custom_call" in compiled.as_text()


def test_channel_sweep_15q_density(one_chip):
    """A noise layer on a 15-qubit density matrix (30 state bits, the
    register's device shape) sweeps in place."""
    chans = (("depol", 0, 15), ("damping", 6, 21), ("depol", 13, 28))
    probs = jax.ShapeDtypeStruct((len(chans),), jnp.float32,
                                 sharding=one_chip)

    def f(x, p):
        return fused.apply_pair_channel_sweep(
            x, chans, [p[i] for i in range(len(chans))], num_bits=N)

    compiled = jax.jit(f, donate_argnums=0).lower(
        _state(one_chip), probs).compile()
    _in_place(compiled)


@pytest.mark.parametrize("reader", ["total_prob", "prob_q0", "prob_q14",
                                    "prob_q29", "get_amp"])
def test_readers_read_in_place_30q(one_chip, reader):
    a = _state(one_chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    if reader == "total_prob":
        lowered = CALC.calc_total_prob_statevec.lower(a)
    elif reader == "get_amp":
        lowered = E._get_pair_canonical.lower(a, i32, i32, i32)
    else:
        lowered = CALC.calc_prob_of_outcome_statevec.lower(
            a, num_qubits=N, target=int(reader[6:]), outcome=0)
    ma = lowered.compile().memory_analysis()
    assert ma.temp_size_in_bytes < SMALL, ma.temp_size_in_bytes


def test_state_fill_on_device_30q(one_chip):
    """initZeroState & co. build the state on the chip in its device
    shape: one state of output, nothing else."""
    fill = K._fill_fn(device_amps_shape(N), np.dtype(np.float32), "basis",
                      one_chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ma = fill.lower(i32).compile().memory_analysis()
    assert ma.output_size_in_bytes == STATE_BYTES
    assert ma.temp_size_in_bytes < SMALL, ma.temp_size_in_bytes


def test_megakernel_refused_on_v5e_and_not_planned(one_chip, monkeypatch):
    """A megawin group with a k=7 single-side member, once refused by the
    v5e compiler (the member's 5-d block had a unit, implicit dimension),
    compiles now that a k=7 member works on its 4-d block, and sweeps the
    state in place; the default still plans no groups, on the TPU too:
    no chip measurement has shown a group beating its per-pass kernels."""
    n = 17
    spec = ((fused.LANE_QUBITS, 1, False, True, False),
            (fused.LANE_QUBITS + 1, 1, False, True, False))
    m = jax.ShapeDtypeStruct((1, 2, 128, 128), jnp.float32, sharding=one_chip)

    def f(x, *ops):
        return fused._apply_megawin_jit(x, *ops, num_qubits=n, spec=spec,
                                        interpret=False)

    compiled = jax.jit(f, donate_argnums=0).lower(
        _state(one_chip, n), m, m, m, m).compile()
    _in_place(compiled, 2 * (1 << n) * 4)
    assert "tpu_custom_call" in compiled.as_text()
    monkeypatch.delenv("QT_MEGAKERNEL", raising=False)
    assert not fused.megakernel_planning()


@pytest.fixture(scope="module")
def drain32(mesh4):
    """chip_smoke's phase-1 circuit at 32 qubits sharded over the
    four-chip mesh (8 GiB per chip), planned by the fusion drain and
    compiled.  Four of its 20 layers: every layer plans the same kinds
    of parts, and the compile stays short."""
    env = QuESTEnv(mesh=mesh4, rank=0, num_ranks=4, seeds=(),
                   topology=TOPO.resolve(4))
    q = Qureg(N32, env, is_density_matrix=False)
    fusion.start_gate_fusion(q)
    chip_smoke.apply_circuit(qt, q, chip_smoke.random_circuit(N32, layers=4))
    program, arrays, _fp, nloc, nsh = fusion.plan_items_quiet(
        q, list(q._fusion.gates))
    runner = fusion._plan_runner(nloc, program, mesh4, "highest",
                                 PAR.exchange_config_key(), 0)
    rep = NamedSharding(mesh4, P())
    arr = tuple(jax.ShapeDtypeStruct(np.shape(a), jnp.float32, sharding=rep)
                for a in arrays)
    state = _state(NamedSharding(mesh4, P(None, AMP_AXIS)), N32, 2)
    compiled = runner.lower(state, arr, ()).compile()
    pred = governor.predict_drain(q, program, arrays, nloc=nloc, nsh=nsh)
    return program, nloc, compiled, pred


def test_sharded_drain_runner_32q_fits_four_chips(drain32):
    """Each chip holds its 8 GiB shard in place; the window remaps'
    half-shard exchanges are collective-permutes beside the window
    kernels, and their chunk transients leave the program inside HBM."""
    program, nloc, compiled, _pred = drain32
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == SHARD32_BYTES
    assert ma.temp_size_in_bytes < SHARD32_BYTES // 2, ma.temp_size_in_bytes
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes
            <= V5E_HBM_BYTES)
    txt = compiled.as_text()
    assert "collective-permute" in txt
    assert "tpu_custom_call" in txt
    swapped = [lb for part in program if part[0] == "remap"
               for lb, _mb in PAR.decompose_sigma(part[1], nloc, 2)[0]]
    assert swapped and min(swapped) >= 14   # block bits: in-place swaps


def test_governor_prediction_matches_compiler_32q(drain32):
    _program, _nloc, compiled, pred = drain32
    ma = compiled.memory_analysis()
    actual = (ma.argument_size_in_bytes + ma.output_size_in_bytes
              - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert abs(pred["predicted_peak_bytes"] - actual) <= 0.1 * actual
    assert pred["exchange_chunks"] == PAR.MAX_EXCHANGE_CHUNKS
    assert pred["fits"]


def test_pipelined_exchange_transient_below_monolithic(mesh4):
    """On the four-chip v5e mesh the chunked exchange's temporaries
    undercut the monolithic one (whose recv and staging buffers are whole
    shards) at a 512 MiB shard — the TPU half of test_distributed_hlo's
    TestPipelinedExchange pin (on the CPU the chunk count is pinned)."""
    n = 28
    shard_bytes = 2 * (1 << (n - 2)) * 4
    amps = jax.ShapeDtypeStruct((2, 1 << n), jnp.float32,
                                sharding=NamedSharding(mesh4,
                                                       P(None, AMP_AXIS)))
    mat = jax.ShapeDtypeStruct((2, 2, 2), jnp.float32,
                               sharding=NamedSharding(mesh4, P()))

    def temp(chunks):
        compiled = PAR._apply_matrix_1q_sharded.lower(
            amps, mat, mesh=mesh4, num_qubits=n, target=n - 1, controls=(),
            control_states=(), chunks=chunks).compile()
        assert "collective-permute" in compiled.as_text()
        return compiled.memory_analysis().temp_size_in_bytes

    mono = temp(1)
    assert mono >= 2 * shard_bytes
    for c in (2, 4, 8):
        assert temp(c) < mono, c


def test_sharded_z_read_in_place_32q(mesh4):
    """calcExpecPauliSum's I/Z-string read of the 32-qubit state over the
    four-chip mesh: one pass over each 8 GiB shard in its device shape,
    partial sums only (the flat view would be a second state a chip)."""
    state = _state(NamedSharding(mesh4, P(None, AMP_AXIS)), N32, 2)
    rep = NamedSharding(mesh4, P())
    masks = jax.ShapeDtypeStruct((1, 3), jnp.uint32, sharding=rep)
    coeffs = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=rep)
    compiled = PAULI.expec_z_sum.lower(state, masks, coeffs).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes - ma.alias_size_in_bytes \
        >= SHARD32_BYTES
    assert ma.temp_size_in_bytes < SMALL, ma.temp_size_in_bytes
    assert "all-reduce" in compiled.as_text()


def test_canonical_read_remap_in_place_32q(mesh4, monkeypatch):
    """The read that canonicalises a live permutation at 32 qubits over
    four chips: the drain leaves block bits permuted among themselves,
    and the remap back runs as ordered half-shard swaps in place (an
    axis permutation would copy the 8 GiB shard, which the chip's HBM
    budget, stated here as on the chip, has no room for)."""
    monkeypatch.setenv("QT_HBM_BUDGET_BYTES", str(V5E_HBM_BYTES))
    env = QuESTEnv(mesh=mesh4, rank=0, num_ranks=4, seeds=(),
                   topology=TOPO.resolve(4))
    q = Qureg(N32, env, is_density_matrix=False)
    fusion.start_gate_fusion(q)
    chip_smoke.apply_circuit(qt, q, chip_smoke.random_circuit(N32, layers=4))
    _program, _arrays, final_perm, nloc, nsh = fusion.plan_items_quiet(
        q, list(q._fusion.gates))
    sigma = PAR.canonical_sigma(tuple(final_perm))
    mixed, local_perm, _tau = PAR.decompose_sigma(sigma, nloc, nsh)
    assert local_perm is None and mixed
    chunks = PAR.remap_chunk_plan(nloc, 4, backend="tpu")
    state = _state(NamedSharding(mesh4, P(None, AMP_AXIS)), N32, 2)
    compiled = PAR._remap_sharded.lower(
        state, mesh=mesh4, num_qubits=N32, sigma=sigma,
        chunks=chunks).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == SHARD32_BYTES
    assert SHARD32_BYTES + ma.temp_size_in_bytes <= V5E_HBM_BYTES, \
        ma.temp_size_in_bytes
    assert "collective-permute" in compiled.as_text()
