"""From a profiler trace to the benchmark's device numbers.

Two halves, kept apart so that the arithmetic can be tested on a small
recorded trace without the profiler:

* :func:`load_xspace` reads one ``*.xplane.pb`` into a :class:`Trace` of
  plain tuples: per device the ops of its ``XLA Ops`` line and the
  program runs of its ``XLA Modules`` line, and the host's ``bench.*``
  annotations. ``jax.profiler.ProfileData`` gives the events; it does not
  give an op's ``tf_op`` stat (the jit path with its ``named_scope``s), so
  that is read from the planes' event metadata by a small protobuf walk.
* The reductions (:func:`busy_s`, :func:`filter_runs`,
  :func:`collective_share`, :func:`breakdown`) work on a :class:`Trace`.

How a TPU v5e trace names things (read by hand from a chip trace):
device planes are ``/device:TPU:<n>``; each op event is named by its HLO
text (``%fusion.3 = s32[216,1920]{...} fusion(...)``) and carries
``device_offset_ps``/``device_duration_ps``; its ``tf_op`` is the jit path
(``jit(scoped)/repro.filter2d.streaming/.../while/body/...``); a
``%while`` op spans the ops of its body on the same line. Program runs
are named ``jit_<fn>(<fingerprint>)``. Host annotations land on the
calling thread's line of the ``/host:CPU`` plane, on the same clock.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

FILTER_SCOPE = "repro.filter2d."
HOST_PREFIX = "bench."
COLLECTIVES = ("collective-permute", "all-gather", "all-reduce",
               "all-to-all", "reduce-scatter", "collective-broadcast",
               "send", "recv")

Op = Tuple[float, float, str, str]      # start_ns, end_ns, hlo name, tf_op
Span = Tuple[float, float, str]         # start_ns, end_ns, name


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Op]
    modules: List[Span]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Span]                    # the benchmark's own annotations

    def window(self, name: str = "bench.window") -> Tuple[float, float]:
        spans = [(s, e) for s, e, n in self.host if n == name]
        if not spans:
            raise ValueError(f"trace has no {name!r} annotation")
        return min(s for s, _ in spans), max(e for _, e in spans)

    def to_json(self) -> dict:
        return {"devices": [dataclasses.asdict(d) for d in self.devices],
                "host": [list(s) for s in self.host]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls([Device(x["name"], [tuple(o) for o in x["ops"]],
                           [tuple(m) for m in x["modules"]])
                    for x in d["devices"]],
                   [tuple(s) for s in d["host"]])

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as fh:
            json.dump(self.to_json(), fh)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as fh:
            return cls.from_json(json.load(fh))


# -- reading an xplane -------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a memoryview, not decoded."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, wire, val


def _tf_ops(plane: memoryview) -> Tuple[str, Dict[str, str]]:
    """A plane's name and, per event-metadata name, its ``tf_op`` stat.

    XPlane: name = 2, event_metadata = 4 (map<int64, XEventMetadata>),
    stat_metadata = 5 (map<int64, XStatMetadata>). XEventMetadata: name =
    2, stats = 5. XStat: metadata_id = 1, str_value = 5, ref_value = 7
    (the id of a stat metadata whose name is the string).
    """
    name = ""
    events, stat_names = [], {}
    for f, _, v in _fields(plane):
        if f == 2:
            name = bytes(v).decode()
        elif f in (4, 5):
            for kf, _, kv in _fields(v):
                if kf != 2:
                    continue
                if f == 4:
                    events.append(kv)
                else:
                    sid, sname = None, ""
                    for sf, _, sv in _fields(kv):
                        if sf == 1:
                            sid = sv
                        elif sf == 2:
                            sname = bytes(sv).decode()
                    stat_names[sid] = sname
    tf_op_ids = {k for k, v in stat_names.items() if v == "tf_op"}
    out: Dict[str, str] = {}
    for meta in events:
        ename, op = "", ""
        for ef, _, ev in _fields(meta):
            if ef == 2:
                ename = bytes(ev).decode(errors="replace")
            elif ef == 5:
                sid, sval = None, None
                for sf, _, sv in _fields(ev):
                    if sf == 1:
                        sid = sv
                    elif sf == 5:
                        sval = bytes(sv).decode(errors="replace")
                    elif sf == 7:
                        sval = stat_names.get(sv, "")
                if sid in tf_op_ids and sval is not None:
                    op = sval
        if op:
            out[ename] = op
    return name, out


def load_xspace(path: str, host_prefix: str = HOST_PREFIX) -> Trace:
    """Read an ``.xplane.pb`` into a :class:`Trace` (times in ns)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as fh:
        raw = memoryview(fh.read())
    tf_ops: Dict[str, Dict[str, str]] = {}
    for f, _, v in _fields(raw):
        if f == 1:
            pname, ops = _tf_ops(v)
            if pname.startswith("/device:"):
                tf_ops[pname] = ops
    data = ProfileData.from_serialized_xspace(bytes(raw))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and plane.name in tf_ops:
            names = tf_ops[plane.name]
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                            names.get(e.name, "")) for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events]
            if ops or modules:
                devices.append(Device(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events
                         if e.name.startswith(host_prefix)]
    devices.sort(key=lambda d: d.name)
    return Trace(devices, sorted(host))


# -- reductions ------------------------------------------------------------

def _clip(spans: Sequence[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in spans if e > lo and s < hi]


def union(spans: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals covering ``spans``."""
    out: List[List[float]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(spans: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in spans)


def busy_s(dev: Device, window: Tuple[float, float]) -> float:
    """Seconds of ``window`` in which some op ran on ``dev``."""
    lo, hi = window
    return _length(union(_clip([(s, e) for s, e, *_ in dev.ops],
                               lo, hi))) * 1e-9


def idle_share(trace: Trace, window: Tuple[float, float]) -> float:
    """1 - busy / window, the mean over the trace's devices."""
    span = (window[1] - window[0]) * 1e-9
    shares = [1.0 - busy_s(d, window) / span for d in trace.devices]
    return sum(shares) / len(shares)


def filter_modules(dev: Device, scope: str = FILTER_SCOPE) -> set:
    """Names of the programs on ``dev`` that ran an op under ``scope``."""
    names = set()
    mods = sorted(dev.modules)
    j = 0
    for s, e, _, op in sorted(dev.ops):
        while j < len(mods) and mods[j][1] < s:
            j += 1
        if j < len(mods) and mods[j][0] <= s and scope in op:
            names.add(mods[j][2])
    return names


def filter_runs(dev: Device, window: Tuple[float, float],
                scope: str = FILTER_SCOPE) -> List[Span]:
    """The runs of the filter's programs that lie wholly in ``window``:
    each is one call of a compiled filter on this device."""
    names = filter_modules(dev, scope)
    lo, hi = window
    return [m for m in dev.modules
            if m[2] in names and m[0] >= lo and m[1] <= hi]


def opcode(hlo_name: str) -> str:
    """The HLO opcode of an op event named by its instruction text
    (``%name = <shape> <opcode>(<operands>)``; a tuple shape is itself in
    parentheses)."""
    _, eq, rest = hlo_name.partition("=")
    rest = rest.strip() if eq else ""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(None, 1)[1] if " " in rest else ""
    return rest.strip().split("(", 1)[0]


def _is_collective(hlo_name: str) -> bool:
    instr = hlo_name.partition("=")[0].strip().lstrip("%")
    return any(x.startswith(c) for x in (opcode(hlo_name), instr)
               for c in COLLECTIVES)


def collective_share(dev: Device, window: Tuple[float, float]) -> Optional[
        float]:
    """Device time in collective ops over device busy time, or None where
    the device ran no collective."""
    lo, hi = window
    coll = [(s, e) for s, e, n, _ in dev.ops if _is_collective(n)]
    if not coll:
        return None
    busy = _length(union(_clip([(s, e) for s, e, *_ in dev.ops], lo, hi)))
    return _length(union(_clip(coll, lo, hi))) / busy if busy else None


def short_name(hlo_name: str, tf_op: str) -> str:
    """``<scope>:<instruction>`` — the op's first named scope (or jit) and
    its HLO instruction name."""
    instr = hlo_name.split("=", 1)[0].strip().lstrip("%") or hlo_name[:40]
    parts = [p for p in tf_op.split("/") if p]
    scope = next((p for p in parts if "." in p and not p.startswith("jit(")),
                 parts[0] if parts else "")
    return f"{scope}:{instr}" if scope else instr


def self_times(ops: Sequence[Op]) -> List[Tuple[str, float]]:
    """(short name, self ns) per op: an op's time less the time of the
    ops nested inside it on the same line (a ``while`` holds its body)."""
    out = []
    stack: List[List] = []            # [end, short name, self ns]
    for s, e, name, op in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            top = stack.pop()
            out.append((top[1], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, short_name(name, op), e - s])
    out += [(t[1], t[2]) for t in stack]
    return out


def _annotation_at(host: Sequence[Span], t: float) -> str:
    """The innermost ``bench.*`` annotation (other than the window) that
    covers host time ``t``."""
    best = None
    for s, e, n in host:
        if s <= t <= e and n != "bench.window":
            if best is None or (e - s) < (best[1] - best[0]):
                best = (s, e, n)
    return best[2] if best else "none"


def breakdown(trace: Trace, window: Tuple[float, float], top: int = 10
              ) -> dict:
    """The device ops with the most self time (seconds, summed over the
    devices and over ops of one name) and the longest idle gaps of the
    first device, each named by what the host was doing in it."""
    lo, hi = window
    tot: Dict[str, float] = {}
    for d in trace.devices:
        inside = [o for o in d.ops if o[0] >= lo and o[1] <= hi]
        for name, ns in self_times(inside):
            tot[name] = tot.get(name, 0.0) + ns
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    if trace.devices:
        busy = union(_clip([(s, e) for s, e, *_ in trace.devices[0].ops],
                           lo, hi))
        edges = [lo] + [x for b in busy for x in b] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_annotation_at(trace.host, (a + b) / 2), b - a))
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[n, ns * 1e-9] for n, ns in ops],
            "idle_gaps": [[n, ns * 1e-9] for n, ns in gaps[:top]]}
