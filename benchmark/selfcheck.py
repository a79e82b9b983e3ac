#!/usr/bin/env python3
"""Self-check of the benchmark, runnable anywhere JAX imports (no chip):

    python3 benchmark/selfcheck.py

1. The byte count of an HLO instruction, on instructions copied from a
   v5e trace, against sizes worked out by hand.
2. The trace reduction (``tracefile.py``) over the traces recorded on a
   v5e chip under ``benchmark/testdata/`` gives the numbers recorded
   beside them, and those numbers hold together: busy time within the
   window, idle time split over host spans adding up to the window less
   the busy time, and every roofline share at most 100 %.
3. A new cell needs only new files and a ``workloads`` entry: a copy of
   the benchmark with a new read kind, traffic mix, limits file and entry
   runs its cell at 14 qubits on the CPU, correct, with no code changed.
"""

import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import run, tracefile  # noqa: E402
from benchmark.metrics import _roofline  # noqa: E402

GIB8 = 2 * 65536 * 128 * 128 * 4        # a 30-qubit float32 state
# (instruction as the trace names it, family, HBM bytes by hand)
INSTRUCTIONS = [
    ("%_apply_window_stack_jit.94 = f32[2,65536,128,128]{3,2,1,0:T(8,128)} "
     "custom-call(f32[2,65536,128,128]{3,2,1,0:T(8,128)} %bitcast.244, "
     "f32[1,256,256]{2,1,0:T(8,128)S(1)} %maximum_maximum_fusion.26, "
     "f32[1,256,256]{2,1,0:T(8,128)S(1)} %maximum_maximum_fusion.27, "
     "f32[2,128,128]{2,1,0:T(8,128)} %arrays_94_.1), custom_call_target="
     "\"tpu_custom_call\", operand_layout_constraints={f32[2,65536,128,128]"
     "{3,2,1,0}, f32[1,256,256]{2,1,0}, f32[1,256,256]{2,1,0}, "
     "f32[2,128,128]{2,1,0}}, output_to_operand_aliasing={{}: (0, {})}",
     "window_pass", 2 * GIB8 + 2 * 128 * 128 * 4),
    ("%_qft_multi_hi_jit.1 = f32[2,256,16,16,128,128]{5,4,3,2,1,0:T(8,128)} "
     "custom-call(f32[2,256,16,16,128,128]{5,4,3,2,1,0:T(8,128)} %bitcast, "
     "f32[4,2,128,128]{3,2,1,0:T(8,128)} %ctab.1, f32[4,2,16]{2,1,0:"
     "T(2,128)} %mlo.1, f32[4,2,1]{2,1,0:T(2,128)S(1)} %copy.1), "
     "custom_call_target=\"tpu_custom_call\"",
     "qft_ladder", 2 * GIB8 + 4 * 2 * 128 * 128 * 4 + 4 * 2 * 16 * 4),
    ("%reshape.2 = f32[2,1,128,8192]{3,2,1,0:T(8,128)S(1)} "
     "reshape(f32[2,64,128,128]{3,2,1,0:T(8,128)} %amps.1)",
     "other", 2 * 64 * 128 * 128 * 4),
]


def check_instructions() -> None:
    table = tracefile.kernel_table()
    for text, fam, nbytes in INSTRUCTIONS:
        base, got = tracefile.parse_instruction(text)
        assert got == nbytes, (base, got, nbytes)
        assert tracefile.classify(base, table) == fam, (base, fam)
    print(f"ok {len(INSTRUCTIONS)} instructions sized and classified")


def check_traces() -> int:
    paths = sorted(glob.glob(os.path.join(HERE, "testdata", "*.xplane.pb")))
    if not paths:
        raise SystemExit("no recorded traces under benchmark/testdata")
    peaks = run.peaks_for("TPU v5 lite")
    for path in paths:
        with open(path[:-len(".xplane.pb")] + ".json") as f:
            want = json.load(f)
        tr = tracefile.load(path)
        got = json.loads(json.dumps(tracefile.summary(tr)))
        for key in ("window_s", "busy_s"):
            assert abs(got[key] - want[key]) < 1e-9, (path, key)
        for key in ("top_ops", "families", "idle_by_span"):
            assert got[key] == want[key], (path, key)
        w0, w1 = tr.window()
        assert 0 < got["busy_s"] <= got["window_s"], path
        idle = sum(s for _n, s in tr.idle_by_span(w0, w1, k=1000))
        assert abs(idle - (got["window_s"] - got["busy_s"])) < 1e-6, path
        ctx = types.SimpleNamespace(trace=tr, w0=w0, w1=w1, peaks=peaks)
        for fam in got["families"]:
            share = _roofline.share(ctx, (fam,))
            assert share is None or 0 < share <= 100.0, (path, fam, share)
        print(f"ok {os.path.basename(path)}: window "
              f"{got['window_s']:.4f} s, busy {got['busy_s']:.4f} s, "
              f"families {sorted(got['families'])}")
    return len(paths)


NEW_READ = '''"""|amplitude|^2 at one index drawn from the seed (qt.getProbAmp)."""

API = "getProbAmp"


class Read:
    def __init__(self, rng, n):
        self.rng, self.n = rng, n

    def spec(self, i):
        return int(self.rng.integers(1 << self.n))

    def program(self, qt, q, k):
        return qt.getProbAmp(q, k)

    def reference(self, ref, k):
        return abs(ref.amplitude(k)) ** 2
'''

NEW_CELL_RUN = '''
import json
from benchmark import run
res, checks, _ = run.run_cell("qft30.selfcheck", 2 ** 31 + 3, 0.5, False,
                              require_chip=False, overrides={"qubits": 14})
print(json.dumps({"correct": res["correct"], "checks": checks}))
'''


def check_new_cell_is_data() -> None:
    """A copy of the benchmark with a new read kind, a new traffic mix, a
    limits file and a ``workloads`` entry, and no code changed, runs its
    new cell at 14 qubits on JAX's CPU backend and compares the new read
    with the reference."""
    tmp = tempfile.mkdtemp(prefix="bench_selfcheck_")
    try:
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
        shutil.copytree(HERE, os.path.join(tmp, "benchmark"),
                        ignore=shutil.ignore_patterns("testdata",
                                                      "__pycache__"))
        os.symlink(os.path.join(ROOT, "quest_tpu"),
                   os.path.join(tmp, "quest_tpu"))
        with open(os.path.join(tmp, "BENCHMARK.json")) as f:
            bench = json.load(f)
        bench["workloads"].append({
            "name": "qft30.selfcheck", "config": "qft30",
            "traffic": "selfcheck_mix", "chips": 1, "why": "self-check"})
        mix = {"prepare": "basis", "params": "fixed",
               "read": "selfcheck_probamp", "check_state": "last",
               "check_reads": "all"}
        files = {"BENCHMARK.json": json.dumps(bench),
                 "benchmark/traffic/selfcheck_mix.json": json.dumps(mix),
                 "benchmark/reads/selfcheck_probamp.py": NEW_READ,
                 "benchmark/limits/qft30.selfcheck.json": json.dumps(
                     {"state_err": 2.5e-6, "read_err": 1e-9})}
        for name, text in files.items():
            with open(os.path.join(tmp, name), "w") as f:
                f.write(text)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable, "-c", NEW_CELL_RUN], cwd=tmp,
                             env=env, capture_output=True, text=True,
                             check=True).stdout.splitlines()[-1]
        got = json.loads(out)
        assert got["correct"] and set(got["checks"]) == {"state_err",
                                                         "read_err"}, got
        print(f"ok a new cell with a new read kind runs from new files "
              f"and a workloads entry: {out}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    check_instructions()
    check_traces()
    check_new_cell_is_data()
