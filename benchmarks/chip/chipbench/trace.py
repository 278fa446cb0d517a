"""Reduction of a ``jax.profiler`` trace to the device's busy and idle
time, per-operation and per-kernel time, and the idle gaps labelled by
what the host was doing. It reads the ``.xplane.pb`` file with
``jax.profiler.ProfileData`` and nothing else.

Device planes are named ``/device:TPU:<i>``; their ``XLA Ops`` line
holds one event per operation the chip ran. The harness brackets its
measured window with a host ``TraceAnnotation`` named ``bench.window``,
and each call into a layer with a ``bench.<layer>`` annotation; the
window's bounds are read from that event, so host and device times are
compared on the trace's own clock."""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# operations whose interval spans the operations of their body, which
# the trace lists as well
CONTAINERS = ("while", "conditional", "call")
DEVICE_PREFIX = "/device:TPU:"
WINDOW = "bench.window"
HOST_PREFIX = "bench."


@dataclass
class Op:
    start: float          # ns, trace clock
    end: float
    name: str
    text: str             # the name and every string stat, for matching


@dataclass
class TraceView:
    """The traced window, its device operations (clipped to it) and the
    harness's own host annotations."""
    lo: float
    hi: float
    devices: dict = field(default_factory=dict)   # plane name -> [Op]
    modules: dict = field(default_factory=dict)   # plane name -> [Op]
    host: list = field(default_factory=list)      # [(start, end, name)]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self, plane: str) -> float:
        """Seconds in which some operation ran on this device: the union
        of its operations' intervals inside the window."""
        return union_ns([(o.start, o.end) for o in self.devices[plane]]) * 1e-9

    def busy_mean_s(self) -> float:
        return sum(self.busy_s(p) for p in self.devices) / len(self.devices)

    def idle_share(self, plane: str) -> float:
        return 1.0 - self.busy_s(plane) / self.window_s

    def max_idle_share(self) -> float:
        """The idle share of the device that was idle the most."""
        return max(self.idle_share(p) for p in self.devices)

    def op_seconds(self, *patterns: str) -> float:
        """Summed device time of the operations whose text (the HLO
        instruction, and any string stats) contains every pattern, over
        all devices."""
        return sum(o.end - o.start for ops in self.devices.values()
                   for o in ops if all(p in o.text for p in patterns)) * 1e-9

    def module_seconds(self, prefix: str = "") -> float:
        """Summed device time of the executables (``XLA Modules``) whose
        name starts with ``prefix``, over all devices."""
        return sum(o.end - o.start for mods in self.modules.values()
                   for o in mods if o.name.startswith(prefix)) * 1e-9

    def module_count(self, prefix: str = "") -> int:
        return sum(1 for mods in self.modules.values() for o in mods
                   if o.name.startswith(prefix))

    def top_ops(self, n: int = 10):
        """[[name, seconds]] of the operations that took the most device
        time, summed over occurrences and devices. Control flow that
        spans its body's operations is left out; names are shortened to
        the instruction, its shape and its opcode."""
        tot = {}
        for ops in self.devices.values():
            for o in ops:
                name = short_name(o.name)
                if name.split(" ")[-1] in CONTAINERS:
                    continue
                tot[name] = tot.get(name, 0.0) + (o.end - o.start) * 1e-9
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10):
        """[[label, seconds]]: device idle time inside the window, summed
        by what the host was doing in each gap (the innermost harness
        annotation that covers the gap's midpoint), largest first. Gaps
        of every device count."""
        tot = {}
        for ops in self.devices.values():
            for a, b in gaps(merge([(o.start, o.end) for o in ops]),
                             self.lo, self.hi):
                label = self.host_label((a + b) / 2)
                tot[label] = tot.get(label, 0.0) + (b - a) * 1e-9
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def host_label(self, t: float) -> str:
        covering = [(s, -e, name) for s, e, name in self.host if s <= t < e]
        return max(covering)[2] if covering else "outside any annotation"


def short_name(hlo: str, width: int = 100) -> str:
    """``%fusion.3 = f32[8,32]{1,0:T(8,128)} fusion(...), ...`` ->
    ``%fusion.3 = f32[8,32] fusion``: the instruction, its result shape
    without layout, and its opcode."""
    if " = " not in hlo:
        return hlo[:width]
    lhs, rhs = hlo.split(" = ", 1)
    out, depth, i = [], 0, 0
    while i < len(rhs):
        ch = rhs[i]
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
        elif depth == 0:
            if ch == "(" and out and out[-1] not in " (,":
                break
            out.append(ch)
        i += 1
    text = "".join(out).strip()
    shape, _, opcode = text.rpartition(" ")
    if len(shape) > width:
        shape = shape[:width] + "..."
    return f"{lhs} = {shape} {opcode}".strip()


def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def gaps(merged, lo, hi):
    """The parts of ``[lo, hi)`` that no merged interval covers."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _text(event) -> str:
    parts = [event.name]
    for _, v in event.stats:
        if isinstance(v, str):
            parts.append(v)
    return "\n".join(parts)


def view(pd, chips: int) -> TraceView:
    """Reduce ``pd`` (a ``ProfileData``) to the window annotated
    ``bench.window`` on the host, and the ``XLA Ops`` of devices
    ``0 .. chips-1``."""
    host, window = [], None
    planes = list(pd.planes)
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(HOST_PREFIX):
                    host.append((e.start_ns, e.end_ns, e.name))
                    if e.name == WINDOW and window is None:
                        window = (e.start_ns, e.end_ns)
    if window is None:
        raise ValueError(f"no host event named {WINDOW!r} in the trace")
    lo, hi = window
    tv = TraceView(lo=lo, hi=hi, host=sorted(host))
    wanted = {f"{DEVICE_PREFIX}{i}" for i in range(chips)}
    for plane in planes:
        if plane.name not in wanted:
            continue
        ops, mods = [], []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                s, t = max(e.start_ns, lo), min(e.end_ns, hi)
                if t > s:
                    (ops if line.name == OPS_LINE else mods).append(
                        Op(s, t, e.name, _text(e)))
        tv.devices[plane.name] = sorted(ops, key=lambda o: o.start)
        tv.modules[plane.name] = sorted(mods, key=lambda o: o.start)
    missing = wanted - set(tv.devices)
    if missing:
        raise ValueError(f"trace has no plane for {sorted(missing)}")
    return tv


def load_view(trace_dir: str, chips: int) -> TraceView:
    from jax.profiler import ProfileData
    return view(ProfileData.from_file(find_xplane(trace_dir)), chips)
