"""The op-name paths of the device ops, and the program's host spans with
their arguments, from a profiler trace.

A TPU trace keeps each device op's op-name path in the metadata of its
event (the ``tf_op`` stat of an ``XEventMetadata``:
``jit(stacked_epoch_scan)/while/body/.../conv2d/jit(_blocked_matmul)/
pallas_call``), not on the event itself, and ``jax.profiler.ProfileData``
shows an event's own stats only. ``metadata_paths`` reads the XSpace wire
format for the event and stat metadata of each ``/device:TPU:<n>`` plane;
``load`` joins the paths to the events by name within their plane. An
event name whose metadata give two paths counts under neither.

The program names its layers with device scopes (``jax.named_scope``),
which appear as components of the path (``.../conv2d/...``, or wrapped by
a transformation, ``vmap(beta_solve)/cholesky``), and with host spans
(``jax.profiler.TraceAnnotation``) on the device ops' clock. A trace of a
program that has neither gives no path under a scope and no span; the
readers then return None.

Everything after ``load`` is plain Python over ``chipbench.trace``
records, so the tests feed it a trace recorded on the chip and
hand-built events alike. A traced run prints one ``oppaths`` line: the
window's device-op time, the part with a path and the part under each
scope.
"""
from __future__ import annotations

import bisect
import functools
import glob
import gzip
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

from chipbench import trace as tr

# xplane.proto field numbers
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_VALUE = 2
EVENT_METADATA_NAME, EVENT_METADATA_STATS = 2, 5
STAT_METADATA_NAME = 2
STAT_METADATA_ID, STAT_STR_VALUE, STAT_REF_VALUE = 1, 5, 7
PATH_STAT = "tf_op"

# the program's device scopes (``repro.scopes``); a reader names its own
SCOPES = ("conv2d", "elm_stats", "beta_solve", "sgd_update", "readout",
          "reduce")
# host spans kept: the program's and the benchmark's own (of the ~1.6M
# host events a 40 s training window holds, the rest are the runtime's)
SPAN_PREFIXES = ("repro.", "bench.")


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of each field of the message in buf[lo:hi]:
    an int for a varint, the (lo, hi) of a length-delimited field; fixed
    width fields are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield field, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf: bytes, span: Tuple[int, int]):
    """The (lo, hi) of the value of one map entry."""
    for f, v in _fields(buf, *span):
        if f == MAP_VALUE:
            yield v


def metadata_paths(raw: bytes) -> Dict[str, Dict[str, Set[str]]]:
    """Device plane name -> event name -> the op-name paths that the
    plane's event metadata give for it (``tf_op``)."""
    out: Dict[str, Dict[str, Set[str]]] = {}
    for f, plane in _fields(raw, 0, len(raw)):
        if f != SPACE_PLANES:
            continue
        name, events, stat_ids = "", [], {}
        for g, v in _fields(raw, *plane):
            if g == PLANE_NAME:
                name = _text(raw, v)
            elif g == PLANE_EVENT_METADATA:
                events.append(v)
            elif g == PLANE_STAT_METADATA:
                for value in _map_values(raw, v):
                    sid, sname = None, ""
                    for h, w in _fields(raw, *value):
                        if h == STAT_METADATA_ID:
                            sid = w
                        elif h == STAT_METADATA_NAME:
                            sname = _text(raw, w)
                    stat_ids[sid] = sname
        if not tr.DEVICE_PLANE.match(name):
            continue
        paths = out.setdefault(name, {})
        for entry in events:
            for value in _map_values(raw, entry):
                ev_name, path = "", None
                for h, w in _fields(raw, *value):
                    if h == EVENT_METADATA_NAME:
                        ev_name = _text(raw, w)
                    elif h == EVENT_METADATA_STATS:
                        stat = dict(_fields(raw, *w))
                        if stat_ids.get(stat.get(STAT_METADATA_ID)) \
                                != PATH_STAT:
                            continue
                        if STAT_STR_VALUE in stat:
                            path = _text(raw, stat[STAT_STR_VALUE])
                        elif STAT_REF_VALUE in stat:    # an interned string
                            path = stat_ids.get(stat[STAT_REF_VALUE])
                if path is not None:
                    paths.setdefault(ev_name, set()).add(path)
    return out


@dataclass(frozen=True)
class Span:
    """A host span and the arguments it was opened with."""
    event: tr.Event
    args: Dict[str, object]


@dataclass
class Trace:
    """Device ops by chip, each with its op-name path ('' where its
    metadata give none or two), the names with two paths by chip, and the
    host spans (``SPAN_PREFIXES``) with their arguments."""
    ops: Dict[int, List[tr.Event]]
    ambiguous: Dict[int, Set[str]]
    spans: List[Span]

    def named(self, name: str, window: Tuple[float, float]) -> List[Span]:
        """The host spans ``name`` that start inside ``window``."""
        lo, hi = window
        return [s for s in self.spans
                if s.event.name == name and lo <= s.event.start_ns < hi]

    def cover(self, window: Tuple[float, float]):
        """Per chip, the union of its device-op intervals in ``window``."""
        lo, hi = window
        return {d: tr.union(tr.clip(((e.start_ns, e.end_ns) for e in evs),
                                    lo, hi))
                for d, evs in self.ops.items()}

    def scope_s(self, scope: str, window: Tuple[float, float]
                ) -> Dict[int, float]:
        """Per chip, the device time of the ops under ``scope`` in
        ``window`` (ops that hold others count toward busy time only)."""
        lo, hi = window
        counted = _per_op(lambda e: under(e, scope) and _leaf(e))
        return {d: tr.length(tr.clip(
            ((e.start_ns, e.end_ns) for e in evs if counted(e)),
            lo, hi)) / 1e9 for d, evs in self.ops.items()}

    def busy_inside(self, intervals: Sequence[Tuple[float, float]],
                    window: Tuple[float, float]) -> List[float]:
        """Per (start, end) interval in ns, the ns of it (inside
        ``window``) in which a device op runs, mean over chips."""
        covers = [(c, [a for a, _ in c])
                  for c in self.cover(window).values()]
        return [sum(_covered(c, starts, a, b) for c, starts in covers)
                / max(len(covers), 1) for a, b in intervals]

    def idle_inside(self, spans: Sequence[Span],
                    window: Tuple[float, float]) -> float:
        """Seconds, mean over chips, in which no device op runs and one
        of ``spans`` is open, inside ``window``."""
        lo, hi = window
        merged = tr.union(tr.clip(
            ((s.event.start_ns, s.event.end_ns) for s in spans), lo, hi))
        busy = self.busy_inside(merged, window)
        return sum(b - a - x for (a, b), x in zip(merged, busy)) / 1e9


def _covered(cover, starts, a: float, b: float) -> float:
    """The length of [a, b] that a sorted disjoint cover covers
    (``starts``: the cover's interval starts)."""
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    total = 0.0
    while i < len(cover) and cover[i][0] < b:
        total += max(0.0, min(cover[i][1], b) - max(cover[i][0], a))
        i += 1
    return total


def _per_op(pred):
    """``pred`` of an op, worked out once per distinct (name, path)."""
    seen: Dict[Tuple[str, str], bool] = {}

    def f(e: tr.Event) -> bool:
        key = (e.name, e.path)
        if key not in seen:
            seen[key] = pred(e)
        return seen[key]
    return f


def _leaf(e: tr.Event) -> bool:
    return tr.opcode(e) not in tr.CONTAINERS


def under(ev: tr.Event, scope: str) -> bool:
    """Whether the op's path names ``scope`` above the op itself, as a
    component or inside a transformation's parentheses."""
    head = ev.path.split("/")[:-1]
    return any(scope in re.split(r"[()]", part) for part in head)


def _read_raw(path: str) -> bytes:
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return f.read()
    with open(path, "rb") as f:
        return f.read()


def load(path: str) -> Trace:
    """The device ops, with their paths, and the program's and the
    benchmark's host spans, with their arguments, of one ``.xplane.pb``
    (or ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    raw = _read_raw(path)
    paths = metadata_paths(raw)
    ops: Dict[int, List[tr.Event]] = {}
    ambiguous: Dict[int, Set[str]] = {}
    spans: List[Span] = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        known = paths.get(plane.name, {})
        for line in plane.lines:
            if m and line.name in tr.OP_LINES:
                d = int(m.group(1))
                for ev in line.events:
                    got = known.get(ev.name, ())
                    if len(got) > 1:
                        ambiguous.setdefault(d, set()).add(ev.name)
                    if ev.duration_ns > 0:
                        ops.setdefault(d, []).append(tr.Event(
                            plane.name, line.name, ev.name,
                            next(iter(got)) if len(got) == 1 else "",
                            float(ev.start_ns), float(ev.duration_ns)))
            elif plane.name.startswith("/host"):
                for ev in line.events:
                    if not ev.name.startswith(SPAN_PREFIXES):
                        continue
                    spans.append(Span(
                        tr.Event(plane.name, line.name, ev.name, "",
                                 float(ev.start_ns), float(ev.duration_ns)),
                        {k: v for k, v in ev.stats}))
    return Trace(ops, ambiguous, spans)


def window_of(t: Trace, name: str) -> Optional[Tuple[float, float]]:
    """The interval of the first host span ``name``."""
    return next(((s.event.start_ns, s.event.end_ns) for s in t.spans
                 if s.event.name == name), None)


@functools.lru_cache(maxsize=1)
def _load_once(path: str, mtime_ns: int, size: int):
    """The trace and its measured window, read once for all the readers
    of a run; prints ``describe`` of the window."""
    from chipbench.harness import TRACE_WINDOW
    t = load(path)
    window = window_of(t, TRACE_WINDOW)
    if t.ops and window is not None:
        print(describe(t, window), flush=True)
    return t, window


def describe(t: Trace, window: Tuple[float, float]) -> str:
    """One line: device-op time in ``window``, the part with a path and
    the part under each scope, in s summed over chips, and the names
    with two paths."""
    lo, hi = window
    leaf = _per_op(_leaf)
    ops = [e for evs in t.ops.values() for e in evs if leaf(e)]
    total = tr.length(tr.clip(
        ((e.start_ns, e.end_ns) for e in ops), lo, hi)) / 1e9
    pathed = tr.length(tr.clip(
        ((e.start_ns, e.end_ns) for e in ops if e.path), lo, hi)) / 1e9
    scoped = {s: sum(t.scope_s(s, (lo, hi)).values()) for s in SCOPES}
    names = sorted(n for ns in t.ambiguous.values() for n in ns)
    return (f"oppaths op_s={total} with_path_s={pathed} under_scope_s="
            f"{scoped} ambiguous={len(names)} {names[:5]}")


def for_reader(metric_file: str, ctx) -> Optional[Trace]:
    """The trace of the run that is reading its metrics, found as the
    harness found it (the newest ``.xplane.pb`` under
    ``harness.trace_dir`` of the checkout that holds ``metric_file``);
    None without a traced window, without a TPU plane, or where that file
    is not the window the harness read."""
    from chipbench import harness
    if ctx.trace is None:
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(metric_file)))))
    found = glob.glob(os.path.join(harness.trace_dir(root), "**",
                                   "*.xplane.pb"), recursive=True)
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    st = os.stat(path)
    t, window = _load_once(path, st.st_mtime_ns, st.st_size)
    if not t.ops or window != tuple(ctx.trace.window_ns):
        return None
    return t
