#!/usr/bin/env python3
"""Record a small trace of one cell on the chip, for the self-check of the
trace reduction (``benchmark/selfcheck.py``).

    python3 benchmark/record_trace.py --workload rc30.sweep --qubits 20 \
        --seconds 3 --out benchmark/testdata/rc20_sweep

writes ``<out>.xplane.pb`` (the traced window of ``benchmark/run.py``
at ``--qubits``) and ``<out>.json`` (its reduction by
``benchmark/tracefile.py``, which the self-check recomputes).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run, tracefile  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--qubits", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    xplane = args.out + ".xplane.pb"
    result, _checks, _ = run.run_cell(
        args.workload, args.seed, args.seconds, True,
        overrides={"qubits": args.qubits, "reference_chunk_bits": 2},
        keep_trace=xplane)
    with open(args.out + ".json", "w") as f:
        json.dump(tracefile.summary(tracefile.load(xplane)), f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
