"""From a profiler trace to plain event records, and from those records to
the numbers the per-layer metrics read.

``load(path)`` reads a ``.xplane.pb`` with ``jax.profiler.ProfileData``
into ``Event`` records: plane, line, name, op-name path, start and
duration in ns. Everything after that is plain Python over records, so
the tests feed it hand-built events as well as a trace recorded on the
chip.

A device operation is an event on an ``XLA Ops`` line of a
``/device:TPU:<n>`` plane, named by its HLO instruction
(``%_blocked_matmul.51 = f32[...] custom-call(...), custom_call_target=
"tpu_custom_call"``). The TPU trace of jax 0.9 carries no op-name path
(no ``tf_op`` stat), so a layer is found by the instruction's own name
(a Pallas kernel is named after its jitted function), its custom-call
target, or an op-name path where a trace has one. Ops that hold others
(``while``, ``conditional``, ``call``) count toward busy time only.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINES = ("XLA Ops",)
PATH_STATS = ("tf_op", "name", "long_name")

# layer -> substrings of an op's instruction name, custom-call target or
# op-name path: the conv2d and elm_stats Pallas kernels, the β solve's
# Cholesky and diagonal-block inversion calls, and the collectives
LAYERS: Dict[str, Tuple[str, ...]] = {
    "conv2d": ("_blocked_matmul", "_conv2d_valid"),
    "elm_stats": ("_elm_stats",),
    "beta_solve": ("Cholesky", "InvertDiagBlocksLowerTriangular",
                   "cholesky", "triangular-solve", "triangular_solve"),
    "collective": ("all-reduce", "all_reduce", "all-gather",
                   "collective-permute", "reduce-scatter"),
}
CONTAINERS = ("while", "conditional", "call")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9\-]*)\(")


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    path: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(path: str) -> List[Event]:
    """The device ops and host events of one ``.xplane.pb`` (or a gzipped
    ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            ops = DEVICE_PLANE.match(plane.name) and line.name in OP_LINES
            if not (ops or plane.name.startswith("/host")):
                continue
            for ev in line.events:
                op_path = ""
                if ops:
                    stats = dict(reversed(list(ev.stats)))
                    op_path = next((str(stats[k]) for k in PATH_STATS
                                    if k in stats), "")
                out.append(Event(plane.name, line.name, ev.name, op_path,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def device_id(ev: Event) -> Optional[int]:
    m = DEVICE_PLANE.match(ev.plane)
    return int(m.group(1)) if m else None


def device_ops(events: Iterable[Event]) -> Dict[int, List[Event]]:
    """Device operations by chip."""
    out: Dict[int, List[Event]] = {}
    for ev in events:
        d = device_id(ev)
        if d is not None and ev.line in OP_LINES and ev.dur_ns > 0:
            out.setdefault(d, []).append(ev)
    return out


def clip(intervals: Iterable[Tuple[float, float]], lo: float, hi: float):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint cover of the intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(cover: Sequence[Tuple[float, float]], lo: float, hi: float):
    """The idle intervals of [lo, hi] outside a disjoint sorted cover."""
    at = lo
    for a, b in cover:
        if a > at:
            yield at, a
        at = max(at, b)
    if hi > at:
        yield at, hi


def instruction(ev: Event) -> str:
    """The HLO instruction name without its number: ``_blocked_matmul``
    of ``%_blocked_matmul.51 = ...``; the event name where it is not
    HLO text."""
    head = ev.name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def opcode(ev: Event) -> str:
    """The HLO opcode (``fusion``, ``custom-call``, ``while``, ...)."""
    body = ev.name.split(" = ", 1)
    m = _OPCODE.search(body[1]) if len(body) == 2 else None
    return m.group(1) if m else ""


def kind(ev: Event) -> str:
    """What a breakdown calls an op: the kernel or custom-call target for
    custom calls, the opcode otherwise."""
    m = _TARGET.search(ev.name)
    if m and m.group(1) != "tpu_custom_call":
        return m.group(1)
    if m:
        return instruction(ev)
    return opcode(ev) or instruction(ev)


def layer_of(ev: Event) -> Optional[str]:
    m = _TARGET.search(ev.name)
    text = " ".join((ev.path, instruction(ev), m.group(1) if m else ""))
    for layer, keys in LAYERS.items():
        if any(k in text for k in keys):
            return layer
    return None


@dataclass
class Summary:
    """What a traced window holds, per chip and over all of them."""
    window_ns: Tuple[float, float]
    busy_s: Dict[int, float]
    layer_s: Dict[int, Dict[str, float]]
    exposed_collective_s: Dict[int, float]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def chips(self) -> int:
        return len(self.busy_s)

    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)

    def total_layer_s(self, layer: str) -> float:
        return sum(v.get(layer, 0.0) for v in self.layer_s.values())


MAIN_THREAD = "python3"


def _label_gap(a: float, b: float, spans: Sequence[Event]) -> str:
    """What the host was doing in an idle gap: the innermost span of the
    main Python thread that covers the gap's midpoint, else of any
    host thread."""
    mid = (a + b) / 2
    inside = [s for s in spans if s.start_ns <= mid <= s.end_ns]
    main = [s for s in inside if s.line == MAIN_THREAD]
    if not inside:
        return "no host span"
    return min(main or inside, key=lambda s: s.dur_ns).name


def summarize(events: Sequence[Event], window: Tuple[float, float],
              top: int = 10) -> Summary:
    """Busy time (the union of op intervals), device time by layer, the
    collective time during which no other op runs on that chip, the ops
    that took most time and the longest idle gaps, all clipped to
    ``window`` (ns)."""
    lo, hi = window
    ops = device_ops(events)
    busy, layers, exposed = {}, {}, {}
    by_name: Dict[str, float] = {}
    idle: List[Tuple[str, float]] = []
    host = [ev for ev in events if device_id(ev) is None and ev.dur_ns > 0]
    for d, evs in sorted(ops.items()):
        cover = union(clip(((e.start_ns, e.end_ns) for e in evs), lo, hi))
        busy[d] = length(cover) / 1e9
        per: Dict[str, float] = {}
        coll, other = [], []
        for e in evs:
            span = list(clip([(e.start_ns, e.end_ns)], lo, hi))
            if not span or opcode(e) in CONTAINERS:
                continue
            layer = layer_of(e)
            dur = span[0][1] - span[0][0]
            if layer is not None:
                per[layer] = per.get(layer, 0.0) + dur / 1e9
            (coll if layer == "collective" else other).append(span[0])
            key = f"{layer or 'other'}:{kind(e)}"
            by_name[key] = by_name.get(key, 0.0) + dur / 1e9
        layers[d] = per
        compute = union(other)
        exposed[d] = sum(length(gaps(compute, a, b))
                         for a, b in union(coll)) / 1e9
        if d == min(ops):
            longest = sorted(gaps(cover, lo, hi), key=lambda g: g[0] - g[1])
            idle = [(_label_gap(a, b, host), (b - a) / 1e9)
                    for a, b in longest[:top]]
    tops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window, busy, layers, exposed, tops, idle)


def window_of(events: Sequence[Event], name: str) -> Tuple[float, float]:
    """The host interval of the annotation ``name`` (the measured
    window)."""
    spans = [ev for ev in events if device_id(ev) is None
             and ev.name == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    return spans[0].start_ns, spans[0].end_ns
