"""Window megakernel (ISSUE 18, docs/design.md §29).

Covers the acceptance contract:
  * ``group_megawins`` is a PURE regroup of the winfused plan — flattening
    the megawin groups reproduces the ungrouped plan tuple-for-tuple, and
    executing the grouped plan is bit-identical to the per-pass route on
    scalar, 8-shard, batched-bank and density registers;
  * the fallback ladder decomposes bit-identically at every rung:
    QT_MEGAKERNEL=off plans no groups, auto excludes non-TPU backends and
    f64 states, a failed Mosaic lowering probe lands in the degradation
    registry, and a megawin op executed where the kernel is not
    executable falls back to the per-pass sequence;
  * a fused dense window group is ONE apply_window_megastack dispatch
    (call count pinned == megawin group count) and the sharded megawin
    program compiles to ZERO collectives in BOTH arms
    (introspect.audit under CollectiveBudget(exact={}));
  * telemetry routes land in megakernel_dispatch_total{route},
    ``model_drift_total == 0`` in both arms (§21 prices the grouping
    identically), and explainCircuit reports the ``mega`` window kind.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu import circuit as CIRC
from quest_tpu import fusion as F
from quest_tpu import introspect
from quest_tpu import telemetry as T
from quest_tpu.ops import fused

NQ = 14  # smallest register with a full fused window

_SQ2 = 1.0 / np.sqrt(2.0)
H_SOA = np.stack([_SQ2 * np.array([[1.0, 1], [1, -1]]), np.zeros((2, 2))])
CX_SOA = np.stack([
    np.array([[1.0, 0, 0, 0], [0, 1, 0, 0], [0, 0, 0, 1], [0, 0, 1, 0]]),
    np.zeros((4, 4)),
])


@pytest.fixture(scope="module")
def env1():
    return qt.createQuESTEnv(num_devices=1)


@pytest.fixture
def env8(env):
    if env.num_devices < 8:
        pytest.skip("needs the 8-device dryrun mesh")
    return env


@pytest.fixture
def tele():
    mode = T.mode_name()
    T.configure("on")
    T.reset()
    yield
    T.reset()
    T.configure(mode)


@pytest.fixture
def dense(monkeypatch):
    """The dense-window A/B environment: QT_PERM_FAST=off in BOTH arms so
    CNOT ladders fuse into dense windows instead of perm-splitting every
    dense run down to one ungroupable winfused pass."""
    monkeypatch.setenv("QT_PERM_FAST", "off")
    return monkeypatch


def _units(rng, nq, depth):
    """(depth, nq) complex Haar 2x2s."""
    z = (rng.standard_normal((depth, nq, 2, 2))
         + 1j * rng.standard_normal((depth, nq, 2, 2)))
    us = np.empty_like(z)
    for d in range(depth):
        for t in range(nq):
            q, r = np.linalg.qr(z[d, t])
            us[d, t] = q * (np.diag(r) / np.abs(np.diag(r)))
    return us


def _gate_list(nq, depth, rng):
    """Dense Gate list (1q Haar layers + CNOT ladder) for plan tests."""
    us = _units(rng, nq, depth)
    gates = []
    for d in range(depth):
        for t in range(nq):
            gates.append(CIRC.Gate(
                (t,), np.stack([us[d, t].real, us[d, t].imag])))
        for t in range(nq - 1):
            if (d + t) % 2 == 0:
                gates.append(CIRC.Gate((t, t + 1), CX_SOA))
    return gates


def _rand_state(nq, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((2, 1 << nq))
    a /= np.sqrt((a ** 2).sum())
    return jnp.asarray(a, dtype)


def _plan_arms(gates, nq, monkeypatch):
    monkeypatch.setenv("QT_MEGAKERNEL", "off")
    off = CIRC.plan_circuit(gates, nq)
    monkeypatch.setenv("QT_MEGAKERNEL", "on")
    on = CIRC.plan_circuit(gates, nq)
    return off, on


@pytest.fixture(scope="module")
def arms():
    """One (off, on) plan pair shared by every plan-level test: planning
    dominates the suite's runtime, so trace once and reuse."""
    gates = _gate_list(NQ, 8, np.random.default_rng(0))
    old = os.environ.get("QT_MEGAKERNEL")
    try:
        os.environ["QT_MEGAKERNEL"] = "off"
        off = CIRC.plan_circuit(gates, NQ)
        os.environ["QT_MEGAKERNEL"] = "on"
        on = CIRC.plan_circuit(gates, NQ)
    finally:
        if old is None:
            os.environ.pop("QT_MEGAKERNEL", None)
        else:
            os.environ["QT_MEGAKERNEL"] = old
    return off, on


def _apply_layers(q, us, ladder=True):
    nq, depth = us.shape[1], us.shape[0]
    with qt.gateFusion(q):
        for d in range(depth):
            for t in range(nq):
                qt.unitary(q, t, us[d, t])
            if ladder:
                for t in range(nq - 1):
                    if (d + t) % 2 == 0:
                        qt.controlledNot(q, t, t + 1)
    return np.asarray(q.amps)


def _flatten(plan):
    out = []
    for op in plan:
        if op[0] == "megawin":
            out.extend(op[1])
        else:
            out.append(op)
    return out


# ---------------------------------------------------------------------------


class TestGrouping:
    def test_off_plans_no_megawin_on_groups(self, arms):
        off, on = arms
        assert CIRC.stats(off)["megawin"] == 0
        st = CIRC.stats(on)
        assert st["megawin"] > 0 and st["megawin_ops"] > st["megawin"]
        # grouping is a PURE regroup: flattening the groups reproduces
        # the per-pass plan op-for-op (kinds, window offsets, operands)
        flat = _flatten(on)
        assert len(flat) == len(off)
        for a, b in zip(flat, off):
            assert a[0] == b[0]
            for fa, fb in zip(a[1:], b[1:]):
                if isinstance(fa, np.ndarray) or isinstance(fb, np.ndarray):
                    assert np.array_equal(np.asarray(fa), np.asarray(fb))
                else:
                    assert fa == fb

    def test_wide_window_stays_ungrouped(self, arms):
        ops = [op for op in arms[0] if op[0] == "winfused"]
        assert len(ops) >= 3
        # a k=12 pass needs G=32 VMEM block rows — over every row cap, so
        # it must stay on the per-pass route and split its neighbours
        wide = ("winfused", 12) + ops[1][2:]
        grouped = CIRC.group_megawins(
            [ops[0], ops[1], wide, ops[2]], 26)
        assert wide in grouped
        for op in grouped:
            if op[0] == "megawin":
                assert wide not in op[1]

    def test_groups_of_one_left_ungrouped(self, arms):
        ops = [op for op in arms[0] if op[0] == "winfused"]
        assert CIRC.group_megawins([ops[0]], NQ) == [ops[0]]

    def test_plan_key_retraces_on_mode_flip(self, monkeypatch):
        items = [CIRC.Gate((0,), H_SOA)]
        monkeypatch.setenv("QT_MEGAKERNEL", "off")
        k_off = F._plan_key(items, NQ, True)
        monkeypatch.setenv("QT_MEGAKERNEL", "on")
        k_on = F._plan_key(items, NQ, True)
        assert k_off != k_on

    def test_mode_parsing(self, monkeypatch):
        for raw, want in (("on", "on"), ("1", "on"), ("TRUE", "on"),
                          ("off", "off"), ("0", "off"), ("no", "off"),
                          ("auto", "off"), ("bogus", "off")):
            monkeypatch.setenv("QT_MEGAKERNEL", raw)
            assert fused.megakernel_mode() == want
        monkeypatch.delenv("QT_MEGAKERNEL")
        assert fused.megakernel_mode() == "off"


class TestParity:
    def test_scalar_plan_bit_identical(self, arms):
        off, on = arms
        assert CIRC.stats(on)["megawin"] > 0
        # execute_plan consumes (donates) the state: fresh one per arm
        a_off = np.asarray(CIRC.execute_plan(
            _rand_state(NQ, 0), CIRC.plan_to_device(off, jnp.float32),
            NQ))
        a_on = np.asarray(CIRC.execute_plan(
            _rand_state(NQ, 0), CIRC.plan_to_device(on, jnp.float32),
            NQ))
        # same block body, same order: the megakernel is BIT-identical
        assert np.array_equal(a_off, a_on)

    @pytest.mark.slow
    def test_scalar_plan_bit_identical_deep(self, monkeypatch):
        gates = _gate_list(NQ, 10, np.random.default_rng(1))
        off, on = _plan_arms(gates, NQ, monkeypatch)
        assert CIRC.stats(on)["megawin"] > 0
        a_off = np.asarray(CIRC.execute_plan(
            _rand_state(NQ, 1), CIRC.plan_to_device(off, jnp.float32),
            NQ))
        a_on = np.asarray(CIRC.execute_plan(
            _rand_state(NQ, 1), CIRC.plan_to_device(on, jnp.float32),
            NQ))
        assert np.array_equal(a_off, a_on)

    def test_fallback_decomposition_bit_identical(self, arms, monkeypatch):
        """The ladder's bottom rung: a megawin op executed where the
        kernel is not executable decomposes to the per-pass sequence."""
        off, on = arms
        dev = CIRC.plan_to_device(on, jnp.float32)
        monkeypatch.setenv("QT_MEGAKERNEL", "on")  # kernel route
        a_on = np.asarray(CIRC.execute_plan(_rand_state(NQ, 3), dev, NQ))
        monkeypatch.setenv("QT_MEGAKERNEL", "off")  # not executable now
        a_dec = np.asarray(CIRC.execute_plan(_rand_state(NQ, 3), dev, NQ))
        a_off = np.asarray(CIRC.execute_plan(
            _rand_state(NQ, 3), CIRC.plan_to_device(off, jnp.float32), NQ))
        assert np.array_equal(a_dec, a_off)
        assert np.array_equal(a_dec, a_on)

    def test_scalar_drain_parity_routes_and_drift(self, env1, dense, tele):
        us = _units(np.random.default_rng(4), NQ, 6)
        dense.setenv("QT_MEGAKERNEL", "off")
        q = qt.createQureg(NQ, env1)
        qt.initDebugState(q)
        a_off = _apply_layers(q, us)
        assert T.counter_sum("megakernel_dispatch_total", route="mega") == 0
        assert T.counter_total("model_drift_total") == 0
        T.reset()
        dense.setenv("QT_MEGAKERNEL", "on")
        q = qt.createQureg(NQ, env1)
        qt.initDebugState(q)
        a_on = _apply_layers(q, us)
        assert T.counter_sum("megakernel_dispatch_total", route="mega") > 0
        assert T.counter_total("model_drift_total") == 0
        np.testing.assert_allclose(a_on, a_off, atol=1e-10, rtol=0)

    @pytest.mark.slow
    def test_sharded_drain_parity(self, env8, dense, tele):
        """8-shard dryrun: nloc = 15 is the smallest local size whose
        remap windows hold more than one fused window to group."""
        n = 18
        us = _units(np.random.default_rng(5), n, 2)
        dense.setenv("QT_MEGAKERNEL", "off")
        q = qt.createQureg(n, env8)
        qt.initDebugState(q)
        a_off = _apply_layers(q, us)
        assert T.counter_total("model_drift_total") == 0
        T.reset()
        dense.setenv("QT_MEGAKERNEL", "on")
        q = qt.createQureg(n, env8)
        qt.initDebugState(q)
        a_on = _apply_layers(q, us)
        assert T.counter_sum("megakernel_dispatch_total", route="mega") > 0
        assert T.counter_total("model_drift_total") == 0
        np.testing.assert_allclose(a_on, a_off, atol=1e-10, rtol=0)

    def test_batched_bank_parity(self, env1, dense):
        us = _units(np.random.default_rng(6), NQ, 4)
        amps = {}
        for flag in ("off", "on"):
            dense.setenv("QT_MEGAKERNEL", flag)
            bq = qt.createBatchedQureg(NQ, env1, 2)
            qt.initPlusState(bq)
            amps[flag] = _apply_layers(bq, us)
        np.testing.assert_allclose(amps["on"], amps["off"],
                                   atol=1e-10, rtol=0)

    def test_density_parity(self, env1, dense):
        nq = 7  # 14 amplitude qubits: one full fused window
        us = _units(np.random.default_rng(7), nq, 4)
        amps = {}
        for flag in ("off", "on"):
            dense.setenv("QT_MEGAKERNEL", flag)
            q = qt.createDensityQureg(nq, env1)
            qt.initPlusState(q)
            amps[flag] = _apply_layers(q, us)
        np.testing.assert_allclose(amps["on"], amps["off"],
                                   atol=1e-10, rtol=0)


class TestDispatchPins:
    def test_one_megastack_call_per_group(self, arms, monkeypatch):
        """A fused dense window group is ONE kernel dispatch: the call
        count equals the plan's megawin group count exactly."""
        plan = arms[1]
        monkeypatch.setenv("QT_MEGAKERNEL", "on")
        groups = CIRC.stats(plan)["megawin"]
        assert groups > 0
        calls = []
        real = fused.apply_window_megastack

        def spy(amps, subops, **kw):
            calls.append(len(subops))
            return real(amps, subops, **kw)

        monkeypatch.setattr(fused, "apply_window_megastack", spy)
        CIRC.execute_plan(_rand_state(NQ, 8),
                          CIRC.plan_to_device(plan, jnp.float32), NQ)
        assert len(calls) == groups
        assert sum(calls) == CIRC.stats(plan)["megawin_ops"]

    def test_explain_reports_mega_kind(self, env1, monkeypatch):
        gates = _gate_list(NQ, 4, np.random.default_rng(9))
        q = qt.createQureg(NQ, env1)
        monkeypatch.setenv("QT_PERM_FAST", "off")  # dense windows
        monkeypatch.setenv("QT_MEGAKERNEL", "on")
        rep = qt.explainCircuit(q, gates)
        assert rep["totals"]["mega_windows"] > 0
        kinds = {w.get("kind") for w in rep["windows"]}
        assert "mega" in kinds
        assert "mega_windows=" in rep.table()
        monkeypatch.setenv("QT_MEGAKERNEL", "off")
        rep = qt.explainCircuit(q, gates)
        assert rep["totals"]["mega_windows"] == 0


class TestFallbackLadder:
    def test_auto_gates_on_backend_and_dtype(self, monkeypatch):
        """The default ("off"; "auto" now reads as off) plans no megawin
        group on any backend (the v5e compiler refuses members the row
        caps admit); only "on" forms them, and a planned group always
        runs fused."""
        for tpu in (False, True):
            monkeypatch.setattr(fused, "_interpret_default", lambda t=tpu: not t)
            monkeypatch.delenv("QT_MEGAKERNEL", raising=False)
            assert fused.megakernel_mode() == "off"
            assert not fused.megakernel_planning()
            monkeypatch.setenv("QT_MEGAKERNEL", "off")
            assert not fused.megakernel_planning()
            monkeypatch.setenv("QT_MEGAKERNEL", "on")
            assert fused.megakernel_planning()
        assert not hasattr(fused, "megakernel_lowering_ok")


class TestCollectives:
    def test_sharded_megawin_program_zero_collectives(self, env8, arms,
                                                      monkeypatch):
        """The megawin route adds ZERO collectives: the whole group stays
        shard-local, so the compiled shard_map program in BOTH arms has
        an empty collective histogram (the §29 acceptance pin)."""
        from jax.sharding import PartitionSpec as P

        from quest_tpu.env import AMP_AXIS, shard_map

        n, nloc = 17, 14
        off, on = arms  # nloc == NQ: the shared plan pair is shard-local
        assert CIRC.stats(on)["megawin"] > 0
        amps = jax.device_put(_rand_state(n, 10), env8.amp_sharding())
        for plan in (off, on):
            dev = CIRC.plan_to_device(plan, jnp.float32)

            def f(a, _dev=dev):
                def kernel(local):
                    return CIRC.execute_plan(local, _dev, nloc)

                return shard_map(
                    kernel, mesh=env8.mesh,
                    in_specs=(P(None, AMP_AXIS),),
                    out_specs=P(None, AMP_AXIS), check_vma=False)(a)

            with introspect.CollectiveBudget(exact={}):
                introspect.audit(f, amps, donate=True)
