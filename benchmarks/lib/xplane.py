"""From the profiler's `.xplane.pb` to numbers: busy and idle time of
each device, device time by program and by operation, collectives that
run while no compute does, and idle gaps named by what the benchmark's
own host spans (`TraceAnnotation("bench:...")`) were doing.

Two stages, so that the arithmetic can be tested on a small recorded
trace without the profiler: `read_xplane` turns the file into plain
lists, `reduce_trace` turns those into a `Trace`.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

# Operations that only contain others (their time is their bodies').
CONTAINER = re.compile(r"^(while|conditional|call)[.\d]*$")
_HLO = re.compile(r"^%?([^ =]+) = (?:([a-z0-9]+\[[0-9,]*\])|\()")


def op_name(raw: str) -> str:
    """`%copy.80 = bf16[16,24,2048,8,128]{...} copy(...)` ->
    `copy.80 bf16[16,24,2048,8,128]`; other names stay."""
    m = _HLO.match(raw)
    if not m:
        return raw
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
# HLO operations that move data between chips.
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast")


def find_xplane(logdir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def read_xplane(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """planes -> lines -> events, for the device planes and for the host
    lines that hold a benchmark span."""
    from jax.profiler import ProfileData

    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            if device:
                evs = [(op_name(e.name), float(e.start_ns),
                        float(e.duration_ns)) for e in line.events]
            else:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in line.events
                       if e.name.startswith(SPAN_PREFIX)]
            if evs:
                lines.setdefault(line.name, []).extend(evs)
        if lines:
            out[plane.name] = lines
    return out


def union_ns(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def subtract_ns(a: Sequence[Tuple[float, float]],
                b: Sequence[Tuple[float, float]]) -> float:
    """Length of the part of union(a) that union(b) does not cover."""
    a, b = merged(a), merged(b)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


class Trace:
    """What the readers are given. Times in seconds."""

    def __init__(self) -> None:
        self.devices: List[str] = []
        self.window_s = 0.0
        self.busy_s: List[float] = []          # one per device
        self.op_s: Dict[str, float] = {}       # summed over devices;
        #                                        containers left out
        self.module_s: Dict[str, float] = {}   # summed over devices
        self.module_n: Dict[str, int] = {}     # launches on device 0
        self.collective_s = 0.0                # mean over devices
        self.collective_exposed_s = 0.0        # mean over devices
        self.idle_gaps: List[Tuple[str, float]] = []

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s) if self.busy_s else 0.0

    @property
    def busy_total_s(self) -> float:
        """Busy time summed over the devices: what a share of device
        time is a share of."""
        return sum(self.busy_s)

    def ops_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_s.items() if rx.search(n))

    def modules_matching(self, pattern: str) -> Tuple[float, int]:
        rx = re.compile(pattern)
        return (sum(s for n, s in self.module_s.items() if rx.search(n)),
                sum(c for n, c in self.module_n.items() if rx.search(n)))

    def breakdown(self, top: int = 10) -> Dict[str, List[List[Any]]]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n[:64], s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:top]]}


def _clip(evs: Sequence[Event], t0: float, t1: float
          ) -> List[Event]:
    out = []
    for name, s, d in evs:
        e = s + d
        if e <= t0 or s >= t1:
            continue
        s2, e2 = max(s, t0), min(e, t1)
        out.append((name, s2, e2 - s2))
    return out


def reduce_trace(planes: Dict[str, Dict[str, List[Event]]],
                 window_span: str = SPAN_PREFIX + "window") -> Trace:
    """Everything is cut to the `bench:window` host span where the trace
    holds one (the profiler starts before and stops after it), else to
    the extent of the device events."""
    tr = Trace()
    spans: List[Event] = []
    for pname, lines in planes.items():
        if not DEVICE_PLANE.match(pname):
            for evs in lines.values():
                spans.extend(e for e in evs if e[0].startswith(SPAN_PREFIX))
    dev_planes = sorted((p for p in planes if DEVICE_PLANE.match(p)),
                        key=lambda p: int(DEVICE_PLANE.match(p).group(1)))
    win = [e for e in spans if e[0] == window_span]
    if win:
        t0, t1 = win[0][1], win[0][1] + win[0][2]
    else:
        all_ops = [e for p in dev_planes
                   for e in planes[p].get(OPS_LINE, [])]
        if not all_ops:
            return tr
        t0 = min(s for _, s, _ in all_ops)
        t1 = max(s + d for _, s, d in all_ops)
    tr.window_s = (t1 - t0) / 1e9
    coll, exposed = [], []
    for i, p in enumerate(dev_planes):
        ops = _clip(planes[p].get(OPS_LINE, []), t0, t1)
        if not ops:
            continue
        tr.devices.append(p)
        tr.busy_s.append(union_ns([(s, s + d) for _, s, d in ops]) / 1e9)
        for name, _, d in ops:
            if not CONTAINER.match(name.split(" ")[0]):
                tr.op_s[name] = tr.op_s.get(name, 0.0) + d / 1e9
        for name, _, d in _clip(planes[p].get(MODULES_LINE, []), t0, t1):
            tr.module_s[name] = tr.module_s.get(name, 0.0) + d / 1e9
            if i == 0:
                tr.module_n[name] = tr.module_n.get(name, 0) + 1
        c = [(s, s + d) for n, s, d in ops if COLLECTIVE.search(n)]
        k = [(s, s + d) for n, s, d in ops if not COLLECTIVE.search(n)]
        coll.append(union_ns(c) / 1e9)
        exposed.append(subtract_ns(c, k) / 1e9)
        if i == 0:
            tr.idle_gaps = _gaps(ops, spans, t0, t1)
    if coll:
        tr.collective_s = sum(coll) / len(coll)
        tr.collective_exposed_s = sum(exposed) / len(exposed)
    return tr


def _gaps(ops: Sequence[Event], spans: Sequence[Event], t0: float,
          t1: float) -> List[Tuple[str, float]]:
    """The longest idle gaps of one device, each named by the benchmark
    span (other than the window itself) that covers most of it."""
    busy = merged([(s, s + d) for _, s, d in ops])
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    inner = [e for e in spans if e[0] != SPAN_PREFIX + "window"]
    for gs, ge in gaps[:10]:
        best, cover = "no_bench_span", 0.0
        for name, s, d in inner:
            ov = min(ge, s + d) - max(gs, s)
            if ov > cover:
                best, cover = name[len(SPAN_PREFIX):], ov
        named.append((best, (ge - gs) / 1e9))
    return named
