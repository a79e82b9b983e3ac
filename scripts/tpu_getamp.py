"""On-chip proof of layout-safe element access at 28q+ (VERDICT r3 item 3):
after a chained fused-QFT plan leaves the state in the canonical tiled
view, getAmp-class reads (ops/element.get_amp_pair) and a setAmps-class
ranged write (set_amp_range) complete in milliseconds with NO full-state
re-layout — the access pattern that would otherwise need a second
state at 30q.

Correctness oracle: QFT of |0..0> is the uniform state, so EVERY
amplitude must read 2^(-n/2) + 0i at any index.

Writes scripts/tpu_getamp_result.json.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RESULT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tpu_getamp_result.json")


def log(*a):
    print(f"[{time.strftime('%H:%M:%S')}]", *a, flush=True)


def run(n):
    import jax.numpy as jnp
    import numpy as np

    from quest_tpu import circuit as C
    from quest_tpu.models.circuits import zero_state_canonical
    from quest_tpu.ops import element as E

    res = {"n": n}
    log(f"building {n}q chained fused QFT ...")
    t0 = time.time()
    a = zero_state_canonical(n)
    a = C.fused_qft(a, n, 0, n)
    a.block_until_ready()
    res["qft_s"] = round(time.time() - t0, 1)
    log(f"QFT done in {res['qft_s']} s; reading amplitudes ...")

    expect = 2.0 ** (-n / 2)
    rng = np.random.default_rng(0)
    idxs = [0, 1, (1 << n) - 1] + [int(x) for x in
                                   rng.integers(0, 1 << n, size=13)]
    t0 = time.time()
    vals = [np.asarray(E.get_amp_pair(a, i)) for i in idxs]
    res["getamp_16_reads_s"] = round(time.time() - t0, 4)
    err = max(abs(v[0] - expect) + abs(v[1]) for v in vals)
    res["getamp_max_err"] = float(err)
    log(f"16 reads in {res['getamp_16_reads_s']} s, max err {err:.2e}")

    # ranged write straddling a tile boundary, then read back
    start = (1 << 14) - 3
    vals2 = np.asarray([[0.125] * 6, [-0.25] * 6], np.float32)
    t0 = time.time()
    a = E.set_amp_range(a, start, vals2)
    back = np.asarray(E.get_amp_pair(a, start + 4))
    res["set_plus_read_s"] = round(time.time() - t0, 4)
    res["set_roundtrip_err"] = float(abs(back[0] - 0.125) + abs(back[1] + 0.25))
    log(f"ranged write+read {res['set_plus_read_s']} s, "
        f"err {res['set_roundtrip_err']:.2e}")
    res["ok"] = bool(err < 1e-6 * expect + 1e-9
                     and res["set_roundtrip_err"] < 1e-7)
    return res


def main():
    import jax

    log("claiming device ...")
    devs = jax.devices()
    log(f"devices: {devs}")
    out = {"devices": str(devs), "runs": []}
    for n in (28, 30):
        try:
            out["runs"].append(run(n))
        except Exception as e:  # OOM at 30q would reproduce the old trap
            out["runs"].append({"n": n, "error": repr(e)[:500]})
            log(f"{n}q FAILED: {e!r}")
    out["ok"] = all(r.get("ok") for r in out["runs"])
    with open(RESULT, "w") as f:
        json.dump(out, f, indent=2)
    log(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
