#!/usr/bin/env python3
"""The control of a cell's comparison, judged as a run is judged: the
numbers it compares, over many seeds, each beside its limit from
``benchmark/limits/<workload>.json``, and ``correct``, which has to come
out false.

    python3 benchmark/control.py --workload rc30.sweep --seconds 3 \
        --seeds 101,102,103 --control reference

The control computes in the nearest precision below the one the
configuration states: three bfloat16 passes (``bf16_3x``) where the
configurations state HIGHEST (float32 accuracy).

* ``--control program``: the program with its own ``bf16_3x`` path
  switched on runs the cell's whole path (a window of ``--seconds`` at
  the cell's own load, then the comparison).
* ``--control reference``: the reference, computed at ``bf16_3x``, is
  put in the program's place: it produces the reads and the state of the
  circuits a run with a short window compares (the warm-up circuit and
  one window circuit), and the comparison judges them as it judges the
  program's.

Every seed runs in one process, so set-up is paid once.  One JSON line per
seed; the benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run  # noqa: E402


def reference_control(workload: str, seed: int, *, on_chip: bool = True,
                      overrides=None, limits=None) -> dict:
    """The bf16_3x reference in the program's place, judged:
    {"correct": ..., "checks": ...}."""
    cell = run.load_cell(workload, overrides=overrides, limits=limits)
    fam = cell.family
    stream = run.Stream(cell.mix, fam, seed)
    done = [stream.circuit(i) for i in range(2)]
    snap_at = 0 if cell.mix["check_state"] == "warm" else 1
    snap = None
    start = 0
    while start < len(done):
        end = start + 1
        while end < len(done) and done[end].init is None:
            end += 1
        refs = fam.reference(done[start].init,
                             [c.params for c in done[start:end]], on_chip,
                             precision="bf16_3x")
        for c, ref in zip(done[start:end], refs):
            c.value = stream.read.reference(ref, c.read)
            c.ok, c.t1 = True, 1.0
            if c.index == snap_at:
                snap = (c.index, ref.host_state())
        refs.close()      # frees the control's state before the reference
        start = end
    values, _ = run.verify(cell, stream, done, snap, on_chip)
    correct, checks = run.judge(values, cell.limits, attempted=1, failed=0)
    return {"correct": correct, "checks": checks, "values": values}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", required=True,
                    choices=("program", "reference"))
    args = ap.parse_args(argv)
    if args.control == "program":
        from quest_tpu.ops import fused

        fused.set_matmul_precision("bf16_3x")
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.control == "program":
            result, checks, _ = run.run_cell(args.workload, seed,
                                             args.seconds, False)
            out = {"correct": result["correct"], "checks": checks}
        else:
            import jax

            if jax.devices()[0].platform != "tpu":
                raise SystemExit("needs a TPU")
            out = reference_control(args.workload, seed)
        print(json.dumps({"control": args.control, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
