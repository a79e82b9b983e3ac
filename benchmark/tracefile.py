"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, kernel time and bytes by kernel family,
and the host spans open while the device idled.

The device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds
one event per operation run.  Host spans are the ``TraceAnnotation``
events on the ``/host:CPU`` plane: the benchmark's own (``window``,
``prepare``, ``capture``, ``drain``, ``read``) and the program's
``fusion.*`` spans.  A device event's name is its HLO instruction
(``%_apply_window_stack_jit.55 = f32[...] custom-call(f32[...] %x, ...),
...``).  Each event is put into a family by the regular expressions of
``kernels.json``, matched against the instruction's own name without its
``%`` and numeric suffix (``_apply_window_stack_jit``); an event no
pattern matches is family ``other`` and shows as such in the breakdown.
Bytes of one event are the sizes of its result and of its operands that
live in HBM, read from their shapes in the instruction: an in-place sweep
of the state counts one read and one write of it.

    python3 benchmark/tracefile.py <file.xplane.pb>   # print the reduction
"""

from __future__ import annotations

import json
import os
import re
import sys
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
HOST_SPANS = re.compile(r"^(window|circuit|prepare|capture|drain|read|"
                        r"fusion\.[a-z_.]+)$")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8, "c64": 8, "c128": 16}
_SHAPE = re.compile(r"\b(pred|[sub]8|bf16|f16|[su]16|f32|[su]32|f64|"
                    r"[su]64|c64|c128)\[([0-9,]*)\](\{[^}]*\})?")


def kernel_table(path: str = os.path.join(HERE, "kernels.json")):
    with open(path) as f:
        table = json.load(f)
    return [(fam, [re.compile(p) for p in pats])
            for fam, pats in table["families"].items()]


def shape_bytes(text: str) -> int:
    """Sum of the sizes of the array shapes written in ``text``
    (``f32[2,65536,128,128]{3,2,1,0:T(8,128)}`` style) that live in HBM:
    a layout with a memory space ``S(n)`` (the core's VMEM) moves no HBM
    bytes and is left out."""
    total = 0
    for dt, dims, layout in _SHAPE.findall(text):
        if "S(" in layout:
            continue
        size = _DTYPE_BYTES[dt]
        for d in dims.split(","):
            if d:
                size *= int(d)
        total += size
    return total


def parse_instruction(text: str):
    """(base name, bytes of result and operands) of an HLO instruction
    ``%name.N = <result shape> opcode(<operands>), <attributes>``; the
    attributes (layouts, aliasing) are not counted.  (text, 0) where the
    text is no instruction."""
    lhs, eq, rhs = text.partition(" = ")
    if not eq or not lhs.startswith("%"):
        return text, 0
    base = re.sub(r"\.\d+$", "", lhs[1:])
    # the opcode's "(" is the first one outside the result's braces
    depth, open_at = 0, -1
    for i, ch in enumerate(rhs):
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
        elif ch == "(" and depth == 0 and i and rhs[i - 1] not in " ,(":
            open_at = i
            break
    if open_at < 0:
        return base, 0
    depth, close_at = 0, len(rhs)
    for i in range(open_at, len(rhs)):
        depth += rhs[i] == "("
        depth -= rhs[i] == ")"
        if depth == 0:
            close_at = i
            break
    result = rhs[:open_at].rsplit(" ", 1)[0]
    return base, shape_bytes(result) + shape_bytes(rhs[open_at:close_at])


@dataclass
class Op:
    device: int
    name: str
    start: float   # seconds
    end: float
    family: str
    nbytes: int    # HBM bytes of its result and operands


@dataclass
class Span:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    ops: list = field(default_factory=list)
    spans: list = field(default_factory=list)
    devices: list = field(default_factory=list)

    # -- windows --------------------------------------------------------------
    def window(self):
        """(start, end) of the benchmark's ``window`` span."""
        ws = [s for s in self.spans if s.name == "window"]
        if not ws:
            raise ValueError("trace has no 'window' span")
        return ws[0].start, ws[-1].end

    def busy(self, w0: float, w1: float) -> float:
        """Seconds in [w0, w1] in which an operation ran, averaged over
        the devices that ran any."""
        per = []
        for d in self.devices:
            iv = _union([(max(o.start, w0), min(o.end, w1))
                         for o in self.ops if o.device == d
                         and o.end > w0 and o.start < w1])
            per.append(sum(b - a for a, b in iv))
        return sum(per) / len(per) if per else 0.0

    def by_family(self, w0: float, w1: float):
        """{family: [seconds, HBM bytes, events]}."""
        out = {}
        for o in self.ops:
            if o.end <= w0 or o.start >= w1:
                continue
            acc = out.setdefault(o.family, [0.0, 0, 0])
            acc[0] += o.end - o.start
            acc[1] += o.nbytes
            acc[2] += 1
        return out

    def span_seconds(self, names, w0: float, w1: float) -> float:
        return sum(min(s.end, w1) - max(s.start, w0) for s in self.spans
                   if s.name in names and s.end > w0 and s.start < w1)

    def top_ops(self, w0: float, w1: float, k: int = 10):
        """The k instructions (``family:base name``) with the most device
        seconds in [w0, w1]."""
        acc = {}
        for o in self.ops:
            if o.end <= w0 or o.start >= w1:
                continue
            key = f"{o.family}:{o.name}"
            acc[key] = acc.get(key, 0.0) + (o.end - o.start)
        return sorted(([n, s] for n, s in acc.items()),
                      key=lambda x: -x[1])[:k]

    def idle_by_span(self, w0: float, w1: float, k: int = 10):
        """Idle device seconds in [w0, w1] (first device), attributed to
        the innermost host span open at each instant; ``none`` where no
        span was open."""
        if not self.devices:
            return []
        d = self.devices[0]
        busy = _union([(max(o.start, w0), min(o.end, w1)) for o in self.ops
                       if o.device == d and o.end > w0 and o.start < w1])
        gaps, t = [], w0
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < w1:
            gaps.append((t, w1))
        spans = sorted((s for s in self.spans if s.name != "window"),
                       key=lambda s: s.start)
        acc = {}
        for g0, g1 in gaps:
            cuts = {g0, g1}
            for s in spans:
                if s.end > g0 and s.start < g1:
                    cuts.update(x for x in (s.start, s.end) if g0 < x < g1)
            cuts = sorted(cuts)
            for a, b in zip(cuts, cuts[1:]):
                mid = 0.5 * (a + b)
                inner = None
                for s in spans:
                    if s.start <= mid < s.end and (
                            inner is None or s.start >= inner.start):
                        inner = s
                name = inner.name if inner else "none"
                acc[name] = acc.get(name, 0.0) + (b - a)
        return sorted(([n, s] for n, s in acc.items()),
                      key=lambda x: -x[1])[:k]


def _union(iv):
    out = []
    for a, b in sorted(iv):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def load(path: str, table=None) -> Trace:
    """Read an ``.xplane.pb`` file into a Trace (times in seconds)."""
    from jax.profiler import ProfileData

    table = kernel_table() if table is None else table
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    base, nbytes = parse_instruction(ev.name)
                    tr.ops.append(Op(dev, base, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9,
                                     classify(base, table), nbytes))
            if any(o.device == dev for o in tr.ops):
                tr.devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if HOST_SPANS.match(ev.name):
                        tr.spans.append(Span(
                            ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9))
    tr.spans.sort(key=lambda s: s.start)
    return tr


def classify(base: str, table) -> str:
    """Family of an instruction: the first whose pattern matches its base
    name in full."""
    for fam, pats in table:
        if any(p.fullmatch(base) for p in pats):
            return fam
    return "other"


def find_xplane(log_dir: str) -> str:
    hits = []
    for dirpath, _dirs, files in os.walk(log_dir):
        hits += [os.path.join(dirpath, f) for f in files
                 if f.endswith(".xplane.pb")]
    if len(hits) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(hits)}")
    return hits[0]


def summary(tr: Trace) -> dict:
    w0, w1 = tr.window()
    return {"window_s": w1 - w0, "busy_s": tr.busy(w0, w1),
            "families": tr.by_family(w0, w1),
            "top_ops": tr.top_ops(w0, w1),
            "idle_by_span": tr.idle_by_span(w0, w1)}


if __name__ == "__main__":
    print(json.dumps(summary(load(sys.argv[1])), indent=1))
