"""A fixed random-layer circuit applied period after period to a register
sharded over four devices, through ``qt.*`` (gateFusion, drain, Z-string
``calcExpecPauliSum``): the state and every read against a dense
complex128 reference, every period after the first a plan-cache hit that
compiles nothing, the same window-remap exchange count every period, the
Z-string read on the device-shaped shards (never the flat ``Qureg.amps``
view), and the ``fusion.reconcile`` span once per sharded drain.

At 16 qubits over 4 devices each shard holds 2^14 amplitudes, so the
register keeps the canonical (2, 2^(n-14), 128, 128) device shape, and
qubits 14 and 15 choose the device.
"""

import jax
import jax._src.monitoring as jax_monitoring
import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu import telemetry
from quest_tpu.qureg import Qureg

N = 16
LAYERS = 4
SHARDS = 4
ROT = (np.array([[0, 1], [1, 0]], complex),
       np.array([[0, -1j], [1j, 0]], complex),
       np.array([[1, 0], [0, -1]], complex))


def _ops(n=N, layers=LAYERS, seed=20261015):
    """The random-layer family: per layer a rotateX/Y/Z on every qubit, a
    CNOT brickwork from offset layer % 2, and CNOT(q, n-1-q) with
    q = layer % (n // 2); axes and angles drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    ops = []
    for layer in range(layers):
        for q in range(n):
            ops.append(("rot", int(rng.integers(3)), q,
                        float(rng.uniform(0.0, 2.0 * np.pi))))
        for q in range(layer % 2, n - 1, 2):
            ops.append(("cnot", q, q + 1))
        q = layer % (n // 2)
        ops.append(("cnot", q, n - 1 - q))
    return ops


def _apply_period(q, ops):
    rot = (qt.rotateX, qt.rotateY, qt.rotateZ)
    with qt.gateFusion(q):
        for op in ops:
            if op[0] == "rot":
                rot[op[1]](q, op[2], op[3])
            else:
                qt.controlledNot(q, op[1], op[2])


def _dense(ops, psi):
    """Dense complex128 state after ``ops`` (rotations exp(-i a/2 P))."""
    n = int(psi.size).bit_length() - 1
    idx = np.arange(psi.size)
    for op in ops:
        if op[0] == "rot":
            _, axis, t, a = op
            u = np.cos(a / 2) * np.eye(2) - 1j * np.sin(a / 2) * ROT[axis]
            v = psi.reshape(-1, 2, 1 << t)
            psi = np.einsum("ab,ibj->iaj", u, v).reshape(-1)
        else:
            _, c, t = op
            psi = psi[np.where((idx >> c) & 1 == 1, idx ^ (1 << t), idx)]
    assert n == N
    return psi


def _z_expectation(psi, codes):
    z = [q for q, c in enumerate(codes) if c == 3]
    par = np.zeros(psi.size, np.int64)
    for q in z:
        par ^= (np.arange(psi.size) >> q) & 1
    return float(np.sum(np.abs(psi) ** 2 * (1 - 2 * par)))


def _state(q):
    a = np.asarray(q.amps, np.float64)
    return a[0] + 1j * a[1]


@pytest.fixture
def compiles():
    """Backend compiles since the fixture started, as a one-item list."""
    box = [0]

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            box[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    yield box
    jax_monitoring.unregister_event_duration_listener(on_duration)


@pytest.fixture(autouse=True)
def telemetry_on():
    """The counters and spans these tests read, whatever QT_TELEMETRY
    says."""
    prev = telemetry.mode_name()
    if prev == "off":
        telemetry.configure("on")
    yield
    telemetry.configure(prev)


@pytest.fixture
def made():
    """Registers and environments a test creates, each destroyed when it
    ends, passed or failed: ``made.env(k)``, ``made.qureg(env)``."""
    class Made:
        def __init__(self):
            self.envs, self.quregs = [], []

        def env(self, k):
            self.envs.append(qt.createQuESTEnv(num_devices=k))
            return self.envs[-1]

        def qureg(self, env):
            self.quregs.append((qt.createQureg(N, env), env))
            return self.quregs[-1][0]

    m = Made()
    yield m
    for q, env in m.quregs:
        qt.destroyQureg(q, env)
    for env in m.envs:
        qt.destroyQuESTEnv(env)


def _zcodes(seed=7):
    rng = np.random.default_rng(seed)
    codes = np.where(rng.random(N) < 0.5, 3, 0)
    codes[[0, N - 2, N - 1]] = 3     # both shard bits and a lane bit
    return [int(c) for c in codes]


def test_floquet_periods_match_dense_and_repeat_the_plan(made, compiles):
    ops = _ops()
    codes = _zcodes()
    q = made.qureg(made.env(SHARDS))
    assert q.device_shape() == (2, 1 << (N - 14), 128, 128)
    qt.initZeroState(q)
    psi = np.zeros(1 << N, complex)
    psi[0] = 1.0
    per_period = []
    for _period in range(3):
        hits0 = telemetry.counter_total("fusion_plan_cache_hits_total")
        miss0 = telemetry.counter_total("fusion_plan_cache_misses_total")
        ex0 = telemetry.counter_sum("exchanges_total", op="window_remap")
        rd0 = telemetry.counter_sum("exchanges_total", op="remap")
        c0 = compiles[0]
        _apply_period(q, ops)
        left_permuted = q._perm is not None
        got = qt.calcExpecPauliSum(q, codes, [1.0])
        per_period.append((
            telemetry.counter_total("fusion_plan_cache_hits_total") - hits0,
            telemetry.counter_total("fusion_plan_cache_misses_total")
            - miss0,
            telemetry.counter_sum("exchanges_total", op="window_remap")
            - ex0,
            compiles[0] - c0,
            telemetry.counter_sum("exchanges_total", op="remap") - rd0))
        assert left_permuted and q._perm is None
        psi = _dense(ops, psi)
        assert abs(got - _z_expectation(psi, codes)) < 1e-10
        assert np.max(np.abs(_state(q) - psi)) < 1e-10
    first, later = per_period[0], per_period[1:]
    assert first[1] == 1 and first[2] > 0          # planned, remapped
    assert first[4] > 0          # the read's remap back, under its own op
    for hits, misses, exchanges, n_compiles, read_remaps in later:
        assert (hits, misses, n_compiles) == (1, 0, 0)
        assert (exchanges, read_remaps) == (first[2], first[4])


def _prepared(made, shards, ops):
    q = made.qureg(made.env(shards))
    qt.initZeroState(q)
    _apply_period(q, ops)
    return q


def test_sharded_z_read_equals_unsharded_without_the_flat_view(
        made, monkeypatch):
    ops = _ops(layers=2, seed=3)
    one = _prepared(made, 1, ops)
    rows = [_zcodes(s) for s in (1, 2)] + [[0] * N, [3] * N]
    coeffs = [0.5, -1.25, 0.75, 2.0]
    want = [qt.calcExpecPauliSum(one, r, [1.0]) for r in rows]
    want_sum = qt.calcExpecPauliSum(one, np.ravel(rows), coeffs)

    sharded = _prepared(made, SHARDS, ops)
    assert sharded._perm is not None        # the drain left a permutation

    def no_flat_view(self):
        raise AssertionError("the read took the flat Qureg.amps view")

    monkeypatch.setattr(Qureg, "amps", property(no_flat_view,
                                                Qureg.amps.fset))
    got = [qt.calcExpecPauliSum(sharded, r, [1.0]) for r in rows]
    assert sharded._perm is None            # canonicalised by the read
    got_sum = qt.calcExpecPauliSum(sharded, np.ravel(rows), coeffs)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)
    assert abs(got_sum - want_sum) < 1e-12
    assert abs(got[2] - 1.0) < 1e-12       # the identity string


def _span_count(name):
    hist = telemetry.snapshot().get("histograms", {}).get("span_seconds", {})
    return sum(v["count"] for label, v in hist.items()
               if label == f"name={name}")


@pytest.mark.parametrize("shards", [1, SHARDS])
def test_reconcile_span_once_per_sharded_drain(made, shards):
    q = made.qureg(made.env(shards))
    qt.initZeroState(q)
    ops = _ops(layers=1)
    before = _span_count("fusion.reconcile")
    drains = 3
    for _ in range(drains):
        _apply_period(q, ops)
        qt.calcExpecPauliSum(q, _zcodes(), [1.0])
    assert _span_count("fusion.reconcile") - before == (
        drains if shards > 1 else 0)
