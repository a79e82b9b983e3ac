"""The shard exchange as the device trace shows it: the collective
operations that move state between chips (``ppermute``'s
``collective-permute``, and any ``all-to-all`` or ``all-gather``), matched
here by their HLO opcode names and not through ``kernels.json``.

* Time: an asynchronous collective is a ``-start`` event and a ``-done``
  event on one device; the transfer is in flight from the start of the
  first to the end of the second (the longer of the two readings of the
  pair: it takes in the two events themselves).  Pairs are matched in
  order on each device, each ``-done`` with the oldest open ``-start``
  of its opcode.  A synchronous collective is in flight for its own
  event.  A device's exchange time is the union of its intervals in
  flight.
* Bytes: what a chip receives, from the HLO result shape of each
  transfer, counted once, at the ``-done`` (or the synchronous event).
  The trace reader's byte count of an event sums its result and its
  operands in HBM: a ``collective-permute-done`` reads (its result, the
  start's (operand, result) tuple), three times its result; a
  synchronous ``collective-permute`` or ``all-to-all`` twice it; an
  ``all-gather`` over D chips its result and a D-th of it.
"""

START, DONE = "-start", "-done"
OPS = ("collective-permute", "all-to-all", "all-gather")

# ICI peak of one TPU v5e chip: 1,600 Gbit/s of chip-to-chip
# interconnect (Google Cloud documentation, "TPU v5e")
ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}


def _opcode(name: str):
    """(opcode, "start" | "done" | "sync") of a collective's base name,
    or None."""
    for op in OPS:
        if name == op:
            return op, "sync"
        if name == op + START:
            return op, "start"
        if name == op + DONE:
            return op, "done"
    return None


def _result_bytes(op: str, kind: str, nbytes: int, devices: int) -> float:
    """Bytes of the result of one transfer, from the trace reader's bytes
    of its event (result and operands)."""
    if kind == "start":
        return 0.0
    if op == "all-gather":      # operand: a D-th of the result
        shares = (1 + 1 / devices) if kind == "sync" else (2 + 1 / devices)
    else:
        shares = 2 if kind == "sync" else 3
    return nbytes / shares


def ici_peak() -> float:
    """One chip's ICI bytes/s for the device kind this process runs on,
    or None."""
    import jax

    return ICI_BYTES_PER_S.get(jax.devices()[0].device_kind)


def per_device(trace, w0: float, w1: float):
    """{device: (seconds in flight, bytes received)} of the collectives
    that end inside [w0, w1]; {} when the trace has none."""
    devices = max(1, len(trace.devices))
    spans, got, open_ = {}, {}, {}
    for o in sorted(trace.ops, key=lambda o: (o.start, o.end)):
        oc = _opcode(o.name)
        if oc is None:
            continue
        op, kind = oc
        if kind == "start":
            open_.setdefault((o.device, op), []).append(o.start)
            continue
        t0 = o.start
        if kind == "done":
            starts = open_.get((o.device, op))
            if starts:
                t0 = starts.pop(0)
        if o.end <= w0 or o.end > w1:
            continue
        spans.setdefault(o.device, []).append((max(t0, w0), o.end))
        got[o.device] = got.get(o.device, 0.0) + _result_bytes(
            op, kind, o.nbytes, devices)
    out = {}
    for d, iv in spans.items():
        busy, end = 0.0, None
        for a, b in sorted(iv):
            if end is None or a > end:
                busy += b - a
                end = b
            elif b > end:
                busy += b - end
                end = b
        out[d] = (busy, got.get(d, 0.0))
    return out
