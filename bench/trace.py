"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

The JAX profiler writes an ``.xplane.pb``; ``load`` flattens it into
``Event`` records (plane, line, name, start and duration in ns), the only
form the reductions below read. Device planes are ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per executed HLO instruction,
named by its text (``%name = shape op(...)``). Host spans are on the
``/host:CPU`` plane; the benchmark's own are named ``bench.*``, and
``bench.traced`` marks the interval the reductions cover.
"""
from __future__ import annotations

import dataclasses
import re

TRACED_SPAN = "bench.traced"
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
# instructions that contain other instructions: their time is their
# children's, and counting it too would count it twice
_CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


def load(path: str) -> list[Event]:
    """Every event of the device planes' op lines and of the host plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = _DEVICE.match(plane.name)
        if not device and plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 int(e.start_ns), int(e.duration_ns)))
    return out


def op_name(event_name: str) -> str:
    """The instruction's name, ``%paged_decode_attention.12 = ...`` ->
    ``paged_decode_attention.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def op_kind(name: str) -> str:
    """The name without its instruction number: ``copy_fusion.12`` ->
    ``copy_fusion``."""
    return re.sub(r"\.\d+$", "", name)


def traced_interval(events: list[Event]) -> tuple[int, int]:
    spans = [e for e in events if e.name == TRACED_SPAN]
    if not spans:
        raise ValueError(f"no {TRACED_SPAN!r} span in the trace")
    return spans[0].start_ns, spans[0].end_ns


def devices(events: list[Event]) -> list[str]:
    return sorted({e.plane for e in events if _DEVICE.match(e.plane)},
                  key=lambda p: int(_DEVICE.match(p).group(1)))


def device_ops(events: list[Event], plane: str, lo: int, hi: int
               ) -> list[Event]:
    """Leaf ops of one device clipped to ``[lo, hi]`` (containers
    dropped)."""
    out = []
    for e in events:
        if e.plane != plane or e.line != OPS_LINE:
            continue
        if op_kind(op_name(e.name)).split(".")[0] in _CONTAINERS:
            continue
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(Event(e.plane, e.line, e.name, s, t - s))
    return out


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_ns(ops: list[Event]) -> int:
    """Length of the union of the ops' intervals."""
    return sum(t - s for s, t in union([(e.start_ns, e.end_ns)
                                        for e in ops]))


def kernel_ns(ops: list[Event], pattern: str) -> tuple[int, list[str]]:
    """Summed device time of the ops whose instruction name matches
    ``pattern`` (a regex on the name without its number), and the
    distinct names matched."""
    rx = re.compile(pattern)
    total, names = 0, set()
    for e in ops:
        name = op_name(e.name)
        if rx.fullmatch(op_kind(name)):
            total += e.dur_ns
            names.add(name)
    return total, sorted(names)


def top_ops(ops: list[Event], k: int = 10) -> list[list]:
    """The ``k`` op kinds that took most device time: [name, seconds]."""
    acc: dict[str, int] = {}
    for e in ops:
        kind = op_kind(op_name(e.name))
        acc[kind] = acc.get(kind, 0) + e.dur_ns
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(ops: list[Event], host: list[Event], lo: int, hi: int,
              k: int = 10) -> list[list]:
    """The ``k`` longest stretches of ``[lo, hi]`` in which the device
    ran no op, each labelled by the benchmark span (``bench.*``) that
    overlaps it most: [label, seconds]."""
    busy = union([(e.start_ns, e.end_ns) for e in ops])
    gaps, cur = [], lo
    for s, t in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        gaps.append((cur, hi))
    spans = [e for e in host if e.name.startswith("bench.")
             and e.name != TRACED_SPAN]
    out = []
    for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        best, label = 0, "other"
        for e in spans:
            ov = min(t, e.end_ns) - max(s, e.start_ns)
            if ov > best:
                best, label = ov, e.name
        out.append([label, (t - s) / 1e9])
    return out


@dataclasses.dataclass
class Reduced:
    """What the per-layer readers need from one trace."""
    lo_ns: int
    hi_ns: int
    ops: dict            # device plane -> its leaf ops in the interval
    host: list

    @property
    def window_s(self) -> float:
        return (self.hi_ns - self.lo_ns) / 1e9

    @property
    def busy_s(self) -> float:
        """Device-busy seconds, averaged over the chips traced."""
        return sum(busy_ns(v) for v in self.ops.values()) / 1e9 / len(
            self.ops)

    def kernel_s(self, pattern: str) -> tuple[float, list[str]]:
        total, names = 0, set()
        for v in self.ops.values():
            ns, found = kernel_ns(v, pattern)
            total += ns
            names.update(found)
        return total / 1e9, sorted(names)

    def breakdown(self) -> dict:
        all_ops = [e for v in self.ops.values() for e in v]
        first = next(iter(self.ops.values()))
        return {"device_ops": top_ops(all_ops),
                "idle_gaps": idle_gaps(first, self.host, self.lo_ns,
                                       self.hi_ns)}


def reduce(events: list[Event]) -> Reduced:
    lo, hi = traced_interval(events)
    planes = devices(events)
    if not planes:
        raise ValueError("no device plane in the trace")
    host = [e for e in events if e.plane == "/host:CPU"
            and e.start_ns < hi and e.end_ns > lo]
    return Reduced(lo, hi, {p: device_ops(events, p, lo, hi)
                            for p in planes}, host)
