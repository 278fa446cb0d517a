"""The program's own phases in a traced window: its host spans
(``repro.<name>`` annotations, written by ``repro.obs.spans``) and the
``jax.named_scope`` names its device operations carry.

``TraceView`` (``chipbench/trace.py``) keeps the harness's ``bench.*``
host events only, and names device operations by their HLO text. This
module reads the same run's ``.xplane.pb`` once more, found by its
``bench.window`` bounds, and keeps what ``TraceView`` leaves out:

- the ``repro.*`` host events, so that device idle time is put down to
  the innermost ``repro.*`` or ``bench.*`` event the host was in at each
  instant of it. A gap is split where the host moved from one event to
  the next: the serve cell's gaps of 4-6 ms span the tier read-back, the
  next batch's assembly, its put and its dispatch, so labelling a whole
  gap by its midpoint (``TraceView.idle_gaps``) names whichever phase
  holds the middle;
- the ``tf_op`` stat of each device operation's metadata, its scope path
  (``jit(step)/serve.gather/gather``), which ``ProfileData`` does not
  expose, read from the file's protobuf wire format;
- JAX's ``backend_compile*`` host events, the compilations in the window.

A program that records no spans and names no scopes reads as nothing
here, and the metrics built on it report nothing."""
from __future__ import annotations

import bisect
import glob
import os
import tempfile
from dataclasses import dataclass, field

from chipbench.trace import DEVICE_PREFIX, HOST_PREFIX, WINDOW, gaps, merge

PROGRAM_PREFIX = "repro."
COMPILE_PREFIX = "backend_compile"
TRACE_DIRS = "chipbench-trace-*"


@dataclass
class Program:
    """What the program wrote into one traced window."""
    spans: list = field(default_factory=list)     # [(start, end, name)]
    compiles: list = field(default_factory=list)  # [(start, end)]
    scopes: dict = field(default_factory=dict)    # plane -> {op: tf_op}


_CACHE: dict = {}     # (lo, hi) of the window -> Program; the newest only


def load(tv) -> Program:
    """The program's spans, compilations and scopes in the window of
    ``tv``: from the newest ``.xplane.pb`` under the temp dir's
    ``chipbench-trace-*/`` whose ``bench.window`` is exactly
    ``(tv.lo, tv.hi)``. Refuses a window no such file holds."""
    key = (tv.lo, tv.hi)
    if key not in _CACHE:
        from jax.profiler import ProfileData
        pattern = os.path.join(tempfile.gettempdir(), TRACE_DIRS, "**",
                               "*.xplane.pb")
        for path in sorted(glob.glob(pattern, recursive=True),
                           key=os.path.getmtime, reverse=True):
            with open(path, "rb") as f:
                data = f.read()
            pd = ProfileData.from_serialized_xspace(data)
            spans, compiles, window = host_events(pd)
            if window == key:
                _CACHE.clear()
                _CACHE[key] = Program(spans, compiles, op_scopes(data))
                break
        else:
            raise ValueError(f"no trace under {pattern} has the window "
                             f"{key}")
    return _CACHE[key]


def host_events(pd):
    """([(start, end, name)] of the ``repro.*`` and ``bench.*`` host
    events, [(start, end)] of the compilations, the window's bounds)."""
    spans, compiles, window = [], [], None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith((PROGRAM_PREFIX, HOST_PREFIX)):
                    spans.append((e.start_ns, e.end_ns, e.name))
                    if e.name == WINDOW and window is None:
                        window = (e.start_ns, e.end_ns)
                elif e.name.startswith(COMPILE_PREFIX):
                    compiles.append((e.start_ns, e.end_ns))
    return sorted(spans), sorted(compiles), window


def has_spans(tv) -> bool:
    """Whether the program wrote any ``repro.*`` span into the window."""
    return any(name.startswith(PROGRAM_PREFIX)
               for _, _, name in load(tv).spans)


def segments(spans, lo: float, hi: float):
    """([edges], [labels]): ``[lo, hi)`` cut at every edge of ``spans``,
    piece ``i`` (``edges[i]`` to ``edges[i + 1]``) labelled by the
    innermost span covering it (the latest to start, then the first to
    end; the rule of ``TraceView.host_label``)."""
    edges = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                               if lo < t < hi})
    by_start = sorted(spans)
    active, labels, k = [], [], 0
    for t in edges[:-1]:
        while k < len(by_start) and by_start[k][0] <= t:
            active.append(by_start[k])
            k += 1
        active = [sp for sp in active if sp[1] > t]
        labels.append(max(active, key=lambda sp: (sp[0], -sp[1]))[2]
                      if active else "outside any annotation")
    return edges, labels


def idle_by_span(tv) -> dict:
    """{label: seconds}: device idle time in the window, each instant of
    it put down to the innermost ``repro.*`` or ``bench.*`` host event
    covering it. Idle time of every device counts."""
    edges, labels = segments(load(tv).spans, tv.lo, tv.hi)
    out: dict = {}
    for ops in tv.devices.values():
        for a, b in gaps(merge((o.start, o.end) for o in ops), tv.lo,
                         tv.hi):
            i = bisect.bisect_right(edges, a) - 1
            while i < len(labels) and edges[i] < b:
                part = min(b, edges[i + 1]) - max(a, edges[i])
                out[labels[i]] = out.get(labels[i], 0.0) + part * 1e-9
                i += 1
    return out


def idle_seconds(tv, prefix: str) -> float:
    """Device idle seconds put down to spans whose name starts with
    ``prefix``."""
    return sum(s for label, s in idle_by_span(tv).items()
               if label.startswith(prefix))


def span_seconds(tv, prefix: str) -> float:
    """Seconds of the window covered by spans whose name starts with
    ``prefix`` (their union, clipped to the window)."""
    return sum(min(e, tv.hi) - max(s, tv.lo) for s, e in merge(
        (s, e) for s, e, name in load(tv).spans
        if name.startswith(prefix) and e > tv.lo and s < tv.hi)) * 1e-9


def scope_seconds(tv, *scopes: str) -> float:
    """Summed device time of the operations whose scope path (``tf_op``)
    contains any of ``scopes``, over all devices."""
    named = load(tv).scopes
    return sum(o.end - o.start for plane, ops in tv.devices.items()
               for o in ops
               if any(s in named.get(plane, {}).get(o.name, "")
                      for s in scopes)) * 1e-9


def compiles(tv) -> int:
    """Compilations (JAX's ``backend_compile*`` host events) that
    overlap the window."""
    return sum(1 for s, e in load(tv).compiles if e > tv.lo and s < tv.hi)


# The protobuf wire format, as much of it as the XSpace's device planes
# need (tsl/profiler/protobuf/xplane.proto): XSpace.planes = 1;
# XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5 (maps: key 1,
# value 2); XEventMetadata.name = 2, .stats = 5; XStatMetadata.id = 1,
# .name = 2; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7.

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of the message in ``buf[lo:hi]``: an int,
    or a (start, end) span of ``buf`` for length-delimited fields."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _str(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, span):
    return [v for f, v in _fields(buf, *span) if f == 2]


def op_scopes(data: bytes) -> dict:
    """{device plane: {operation name: tf_op}} of a serialized XSpace:
    the scope path each operation's metadata carries, where it has one.
    Two operations of one name with other paths keep both, a line
    each."""
    buf = memoryview(data)
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, events, stats = "", [], {}
        for pf, v in _fields(buf, *plane):
            if pf == 2:
                name = _str(buf, v)
            elif pf == 4:
                events.extend(_map_values(buf, v))
            elif pf == 5:
                for sm in _map_values(buf, v):
                    d = dict(_fields(buf, *sm))
                    stats[d.get(1, 0)] = _str(buf, d[2]) if 2 in d else ""
        if not name.startswith(DEVICE_PREFIX):
            continue
        tf_op = {k for k, v in stats.items() if v == "tf_op"}
        ops = out.setdefault(name, {})
        for ev in events:
            op, path = None, None
            for ef, v in _fields(buf, *ev):
                if ef == 2:
                    op = _str(buf, v)
                elif ef == 5:
                    st = dict(_fields(buf, *v))
                    if st.get(1) in tf_op:
                        path = (_str(buf, st[5]) if 5 in st
                                else stats.get(st.get(7), ""))
            if op is not None and path:
                known = ops.get(op)
                if known is None:
                    ops[op] = path
                elif path not in known.split("\n"):
                    ops[op] = known + "\n" + path
    return out
