"""Reduction of a JAX profiler trace (`.xplane.pb`) to the numbers the
per-layer readers use: device busy time (the union of op intervals), the
traced window, time by kernel name, module calls, collectives not hidden
under compute, and the longest idle gaps labelled with what the host was
doing in them.

Device planes are `/device:TPU:<i>`; their `XLA Ops` line holds one event
per executed HLO op, their `XLA Modules` line one per executed program. The
window is the host span `bench.window` (the benchmark's own annotation)
when the trace has it, else the span of the device ops.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|"
                        r"AllReduce|AllGather|ReduceScatter|"
                        r"CollectivePermute|AllToAll", re.I)


# ops that contain other ops of the same line (a scan's while loop): they
# count towards busy time but never as compute that hides a collective
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Op:
    name: str
    start: float
    end: float
    label: str = ""          # op name plus whatever stats name it
    opcode: str = ""         # HLO opcode parsed from the op's text

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class DeviceTrace:
    name: str
    ops: List[Op] = field(default_factory=list)
    modules: List[Op] = field(default_factory=list)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals `a` not covered by `b`."""
    out, b = [], union(b)
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


@dataclass
class TraceSummary:
    devices: List[DeviceTrace]
    window: Interval
    host: List[Op] = field(default_factory=list)
    _host_index: Optional["_SpanIndex"] = field(default=None, repr=False)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _ops(self, d: DeviceTrace) -> List[Interval]:
        return clip(((o.start, o.end) for o in d.ops), *self.window)

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(total(union(self._ops(d))) for d in self.devices) \
            * 1e-9 / len(self.devices)

    def idle_frac(self) -> Optional[float]:
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def match(self, pattern: str) -> List[Tuple[int, Op]]:
        rx = re.compile(pattern)
        lo, hi = self.window
        return [(i, o) for i, d in enumerate(self.devices) for o in d.ops
                if rx.search(o.label) and o.start >= lo and o.end <= hi]

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of the ops whose label matches, averaged over
        the devices."""
        if not self.devices:
            return 0.0
        return sum(o.dur for _, o in self.match(pattern)) * 1e-9 \
            / len(self.devices)

    def module_calls(self, pattern: str) -> List[Op]:
        """Executions of the programs whose name matches, on device 0."""
        if not self.devices:
            return []
        rx = re.compile(pattern)
        lo, hi = self.window
        return [m for m in self.devices[0].modules
                if rx.search(m.label) and m.start >= lo and m.end <= hi]

    def exposed_collective_s(self) -> float:
        """Seconds in collectives while no other op runs on that device,
        averaged over the devices."""
        if not self.devices:
            return 0.0
        out = 0.0
        for d in self.devices:
            ops = self._ops_with_label(d)
            coll = union(iv for iv, lab, _ in ops if COLLECTIVE.search(lab))
            comp = union(iv for iv, lab, code in ops
                         if not COLLECTIVE.search(lab)
                         and code not in CONTAINERS)
            out += total(subtract(coll, comp))
        return out * 1e-9 / len(self.devices)

    def _ops_with_label(self, d: DeviceTrace):
        lo, hi = self.window
        return [((max(o.start, lo), min(o.end, hi)), o.label, o.opcode)
                for o in d.ops if min(o.end, hi) > max(o.start, lo)]

    def breakdown(self, k: int = 10) -> Dict[str, List]:
        by_name: Dict[str, float] = {}
        for _, o in self.match(""):
            key = short_name(o.name)
            by_name[key] = by_name.get(key, 0.0) + o.dur
        n = max(len(self.devices), 1)
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
        idle: Dict[str, float] = {}
        if self.devices:
            busy = union(self._ops(self.devices[0]))
            for s, e in gaps(busy, *self.window):
                what = self.host_activity((s + e) / 2)
                idle[what] = idle.get(what, 0.0) + (e - s)
        gtop = sorted(idle.items(), key=lambda kv: -kv[1])[:k]
        return {"device_ops": [[name, t * 1e-9 / n] for name, t in top],
                "idle_gaps": [[name, t * 1e-9] for name, t in gtop]}

    def host_activity(self, t: float) -> str:
        """The innermost host span covering time t, or 'no host span'."""
        if self._host_index is None:
            self._host_index = _SpanIndex(
                [o for o in self.host if o.name != "bench.window"])
        best = self._host_index.innermost(t)
        return best.name if best is not None else "no host span"


class _SpanIndex:
    """Host spans bucketed by time, for innermost-span lookups."""

    BIN = 1e6           # ns

    def __init__(self, spans: Sequence[Op]):
        self.bins: Dict[int, List[Op]] = {}
        for o in spans:
            for b in range(int(o.start // self.BIN), int(o.end // self.BIN) + 1):
                self.bins.setdefault(b, []).append(o)

    def innermost(self, t: float) -> Optional[Op]:
        best = None
        for o in self.bins.get(int(t // self.BIN), ()):
            if o.start <= t <= o.end and (best is None or o.dur < best.dur):
                best = o
        return best


_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")


def opcode(text: str) -> str:
    """The HLO opcode of an op's text `%x = <type> <opcode>(...)`."""
    rhs = text.split(" = ", 1)[-1]
    m = _OPCODE.search(" " + rhs)
    return m.group(1) if m else ""


def short_name(text: str) -> str:
    """`%name opcode type` of an op's text, for the breakdown."""
    if " = " not in text:
        return text[:120]
    lhs, rhs = text.split(" = ", 1)
    typ = rhs.split(" ", 1)[0] if not rhs.startswith("(") else "(tuple)"
    return f"{lhs} {opcode(text)} {typ}"[:120]


def _stat_label(ev) -> str:
    parts = [ev.name]
    for k, v in dict(ev.stats).items():
        if k in ("hlo_op", "long_name", "tf_op", "name", "hlo_module",
                 "kernel_details"):
            parts.append(str(v))
    return " ".join(parts)


def load(path: str, n_devices: int) -> TraceSummary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: List[DeviceTrace] = []
    host: List[Op] = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            dt = DeviceTrace(plane.name)
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                dst = dt.ops if line.name == "XLA Ops" else dt.modules
                for ev in line.events:
                    dst.append(Op(ev.name, ev.start_ns, ev.end_ns,
                                  _stat_label(ev), opcode(ev.name)))
            devices.append(dt)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append(Op(ev.name, ev.start_ns, ev.end_ns,
                                       ev.name))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    devices = devices[:n_devices]
    win = [o for o in host if o.name == "bench.window"]
    if win:
        window = (win[0].start, win[0].end)
    else:
        spans = [(o.start, o.end) for d in devices for o in d.ops]
        window = (min(s for s, _ in spans), max(e for _, e in spans)) \
            if spans else (0.0, 0.0)
    return TraceSummary(devices, window, host)


def summarize(trace_dir, n_devices: int) -> TraceSummary:
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        return TraceSummary([], (0.0, 0.0))
    return load(files[-1], n_devices)


def from_json(obj: Dict) -> TraceSummary:
    """A reduced trace from its JSON form (ops, modules and host spans as
    [text, start_ns, end_ns]), as test fixtures keep it."""
    def ops(seq):
        return [Op(n, s, e, n, opcode(n)) for n, s, e in seq]
    devs = [DeviceTrace(d["name"], ops(d["ops"]), ops(d["modules"]))
            for d in obj["devices"]]
    host = [Op(n, s, e, n) for n, s, e in obj["host"]]
    return TraceSummary(devs, tuple(obj["window"]), host)
