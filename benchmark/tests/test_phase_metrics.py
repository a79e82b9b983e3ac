"""The fusion planner's phase metrics: ``materialize_s_per_circuit``,
``schedule_s_per_circuit``, ``drain_host_s_per_circuit`` and
``passes_per_drain``, on a trace recorded on a v5e chip
(``testdata/rc20_sweep_phases``, by ``record_trace.py``), on stub traces,
and on the CPU rehearsal of rc30.sweep at two seeds:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import types

import pytest

from benchmark import run, tracefile
from benchmark.metrics import (drain_host_s_per_circuit,
                               materialize_s_per_circuit, passes_per_drain,
                               plan_s_per_circuit, schedule_s_per_circuit)
from benchmark.tracefile import Span, Trace
from rehearsal_size import SMALL, small_limits

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, os.pardir, "testdata",
                        "rc20_sweep_phases.xplane.pb")
SPAN_METRICS = (materialize_s_per_circuit, schedule_s_per_circuit,
                drain_host_s_per_circuit)


def _ctx(tr, w0, w1, circuits):
    return types.SimpleNamespace(trace=tr, w0=w0, w1=w1, circuits=circuits)


@pytest.fixture(scope="module")
def recorded():
    """The recorded rc20 sweep's window, with the circuits the harness's
    ``circuit`` spans show completed inside it."""
    tr = tracefile.load(RECORDED)
    w0, w1 = tr.window()
    done = sum(1 for s in tr.spans
               if s.name == "circuit" and w0 <= s.start and s.end <= w1)
    return _ctx(tr, w0, w1, done)


def test_recorded_trace_reports_every_span_metric(recorded):
    assert recorded.circuits >= 1
    for mod in SPAN_METRICS:
        v = mod.read(recorded)
        assert v is not None and v >= 0, mod.__name__


def test_recorded_planner_steps_fit_inside_plan(recorded):
    """The planner's steps take no more than the planner and optimizer
    seconds, and they tile ``fusion.plan``: its self time is under 5 %."""
    steps = (materialize_s_per_circuit.read(recorded)
             + schedule_s_per_circuit.read(recorded))
    assert steps <= plan_s_per_circuit.read(recorded)
    plan = recorded.trace.span_seconds(("fusion.plan",), recorded.w0,
                                       recorded.w1) / recorded.circuits
    assert steps >= 0.95 * plan


def test_recorded_drain_host_is_its_phases(recorded):
    """``drain_host_s_per_circuit`` is non-negative, and the drain's own
    phase spans cover at least 90 % of it."""
    host = drain_host_s_per_circuit.read(recorded)
    phases = recorded.trace.span_seconds(
        ("fusion.key", "fusion.govern", "fusion.dispatch"), recorded.w0,
        recorded.w1) / recorded.circuits
    assert host >= 0
    assert host >= phases >= 0.9 * host


def _stub(spans, circuits=2):
    tr = Trace(spans=[Span(n, a, b) for n, a, b in spans])
    return _ctx(tr, 0.0, 100.0, circuits)


DRAIN = [("fusion.drain", 10.0, 30.0), ("fusion.optimize", 10.0, 11.0),
         ("fusion.key", 11.0, 11.5), ("fusion.plan", 11.5, 27.5),
         ("fusion.analyse", 11.5, 12.0), ("fusion.schedule", 12.0, 13.0),
         ("fusion.materialize", 13.0, 27.0), ("fusion.group", 27.0, 27.5),
         ("fusion.govern", 27.5, 28.0), ("fusion.dispatch", 28.0, 29.5)]


@pytest.mark.parametrize("mod,want", [
    (materialize_s_per_circuit, 14.0 / 2),
    (schedule_s_per_circuit, (0.5 + 1.0 + 0.5) / 2),
    (drain_host_s_per_circuit, (20.0 - 1.0 - 16.0) / 2)])
def test_stub_trace_reads(mod, want):
    assert mod.read(_stub(DRAIN)) == pytest.approx(want)


@pytest.mark.parametrize("mod", SPAN_METRICS)
def test_stub_trace_without_the_spans_reads_none(mod):
    """A trace with none of a metric's spans in the window (a program
    that lacks them) leaves the metric out; the parent's spans alone give
    no planner step."""
    assert mod.read(_stub([])) is None
    assert mod.read(_stub([("fusion.materialize", 200.0, 201.0)])) is None
    assert mod.read(_stub(DRAIN, circuits=0)) is None
    parent = [s for s in DRAIN if s[0] in ("fusion.drain", "fusion.optimize",
                                           "fusion.plan")]
    got = mod.read(_stub(parent))
    if mod is drain_host_s_per_circuit:
        assert got == pytest.approx((20.0 - 1.0 - 16.0) / 2)
    else:
        assert got is None


def test_passes_per_drain_reads_the_registry():
    from quest_tpu import telemetry

    telemetry.reset()
    try:
        assert passes_per_drain.read(None) is None
        telemetry.inc("fusion_drains_total", 2)
        assert passes_per_drain.read(None) is None
        telemetry.inc("fusion_passes_total", 118)
        assert passes_per_drain.read(None) == 59.0
    finally:
        telemetry.reset()


def test_passes_per_drain_same_for_two_seeds():
    """The angles change no plan shape: every drain of rc30.sweep at
    14 qubits dispatches the same whole number of passes, whatever the
    seed."""
    from quest_tpu import telemetry

    got = []
    for seed in (2 ** 31 + 77, 5):
        telemetry.reset()
        result, checks, _ = run.run_cell(
            "rc30.sweep", seed, 0.5, False, require_chip=False,
            overrides=SMALL, limits=small_limits("rc30.sweep"))
        assert result["correct"], checks
        got.append(passes_per_drain.read(None))
    telemetry.reset()
    assert got[0] is not None and got[0] > 0
    assert float(got[0]).is_integer()
    assert got[0] == got[1]
