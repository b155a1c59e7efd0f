"""What the program says of itself in a trace, read from the same
`.xplane.pb` as `lib/xplane.py` reads, for what that module drops:

- host events named `ray_tpu:*` (`ray_tpu/util/tracing.span`), with
  their stats (the span's attributes) and their thread;
- the scope path of each device operation (`tf_op` in the event's
  metadata: `jit(train_step)/transpose(jvp(fwd))/while/...`), which
  `jax.profiler.ProfileData` does not hand out, so a few lines here walk
  the file's protobuf wire format for it;
- the `kernel_metadata` a pallas kernel's event carries in its name.

Two stages, like `lib/xplane.py`: `read_profile` turns the file into
plain lists, `reduce_profile` turns those into a `ProgramSpans`. Where
the program has no such span, program name or scope (an older commit),
every accessor gives nothing, and a reader returns None.
"""

from __future__ import annotations

import json
import os
import re
import statistics
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from . import peaks, stats, xplane

SPAN_PREFIX = "ray_tpu:"
WINDOW_SPAN = xplane.SPAN_PREFIX + "window"
# `jit_decode_k8(123)`, `jit_decode_lp_k8(123)`: a block of 8 fused steps.
DECODE_BLOCK = re.compile(r"^jit_decode(?:_lp)?_k(\d+)\b")
KERNEL_CALL = "tpu_custom_call"
KERNEL_NAME = re.compile(r'kernel_metadata=\{\s*"kernel":"(\w+)"')
# Spans in which the engine's thread waits and does no work of its own.
WAITS = ("engine.fetch", "engine.idle_wait")

Event = Tuple[str, float, float]          # name, start_ns, duration_ns


class Span:
    __slots__ = ("name", "start", "dur", "thread", "stats", "depth",
                 "children_ns", "parent")

    def __init__(self, name: str, start: float, dur: float, thread: str,
                 stats: Dict[str, Any]):
        self.name, self.start, self.dur = name, start, dur
        self.thread, self.stats = thread, stats
        self.depth, self.children_ns, self.parent = 0, 0.0, None

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def self_ns(self) -> float:
        return self.dur - self.children_ns


# -- the file ---------------------------------------------------------------

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b: bytes, i: int, end: int) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one protobuf message: a varint's value,
    or the (start, end) of a length-delimited field, which is how a
    nested message is skipped without being read."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(b, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire}")


def scope_paths(path: str) -> Dict[str, str]:
    """Device operation (its event's full name) -> scope path, from the
    `tf_op` stat of the device planes' event metadata. XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5 (maps: the
    value is field 2); XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1, .str_value =
    5, .bytes_value = 6. Lines (field 3) are skipped: `ProfileData`
    reads those."""
    with open(path, "rb") as f:
        b = f.read()
    out: Dict[str, str] = {}
    for f1, plane in _fields(b, 0, len(b)):
        if f1 != 1:
            continue
        name, stat_ids, metas = "", {}, []
        for f2, v in _fields(b, *plane):
            if f2 == 2:
                name = b[v[0]:v[1]].decode()
            elif f2 in (4, 5):
                entry = next((e for k, e in _fields(b, *v) if k == 2), None)
                if entry is None:
                    continue
                if f2 == 4:
                    metas.append(entry)
                else:
                    got = dict(_fields(b, *entry))
                    if 1 in got and 2 in got:
                        stat_ids[got[1]] = b[got[2][0]:got[2][1]].decode()
        if not xplane.DEVICE_PLANE.match(name):
            continue
        scope_id = next((i for i, n in stat_ids.items() if n == "tf_op"),
                        None)
        for meta in metas:
            ev_name, scope = None, None
            for f3, v in _fields(b, *meta):
                if f3 == 2:
                    ev_name = b[v[0]:v[1]].decode()
                elif f3 == 5:
                    stat = dict(_fields(b, *v))
                    if stat.get(1) == scope_id:
                        s = stat.get(5) or stat.get(6)
                        # A value that repeats is a reference to a name.
                        scope = b[s[0]:s[1]].decode() if s \
                            else stat_ids.get(stat.get(7))
            if ev_name and scope:
                out[ev_name] = scope.rstrip(":")
    return out


def _stat(key: str, v: Any) -> Any:
    """The profiler hands every attribute back as text: whole numbers
    become numbers again, lists of ids (`req_ids`) stay text."""
    if key.endswith("_ids"):
        return str(v)
    try:
        return int(v)
    except (TypeError, ValueError):
        return v if isinstance(v, float) else str(v)


def read_profile(path: str) -> Dict[str, Any]:
    """{"spans": [Span], "window": (t0, t1) | None, "devices": {plane:
    {"ops": [Event], "modules": [Event]}}, "scopes": {op: scope path}}."""
    from jax.profiler import ProfileData

    spans: List[Span] = []
    window = None
    devices: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {xplane.OPS_LINE: "ops",
                       xplane.MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key].extend((e.name, float(e.start_ns),
                                     float(e.duration_ns))
                                    for e in line.events)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append(Span(
                        e.name[len(SPAN_PREFIX):], float(e.start_ns),
                        float(e.duration_ns), line.name,
                        {k: _stat(k, v) for k, v in e.stats}))
                elif e.name == WINDOW_SPAN and window is None:
                    window = (float(e.start_ns),
                              float(e.start_ns) + float(e.duration_ns))
    return {"spans": spans, "window": window, "devices": devices,
            "scopes": scope_paths(path) if devices else {}}


# -- the reduction ----------------------------------------------------------

def nest(spans: Sequence[Span]) -> None:
    """Parent, depth and the time its children cover, for each span, by
    thread: a span's children are the spans of its thread that lie inside
    it. Self time is what is left."""
    by_thread: Dict[str, List[Span]] = {}
    for s in spans:
        s.depth, s.children_ns, s.parent = 0, 0.0, None
        by_thread.setdefault(s.thread, []).append(s)
    for group in by_thread.values():
        group.sort(key=lambda s: (s.start, -s.dur))
        stack: List[Span] = []
        for s in group:
            while stack and stack[-1].end <= s.start:
                stack.pop()
            if stack:
                s.parent, s.depth = stack[-1], stack[-1].depth + 1
                stack[-1].children_ns += min(s.end, stack[-1].end) - s.start
            stack.append(s)


def phase_of(scope: Optional[str]) -> Optional[str]:
    """forward, backward or optimizer, from a train-step operation's
    scope path: `optimizer` names itself; whatever autodiff transposed
    is backward (remat recomputation included); the rest of what
    `loss_fn` marked `fwd` or `loss_head` is forward."""
    if not scope:
        return None
    parts = scope.split("/")
    if "optimizer" in parts:
        return "opt"
    if any(p.startswith("transpose(") for p in parts):
        return "bwd"
    if any(re.fullmatch(r"(jvp\()?(fwd|loss_head)\)?", p) for p in parts):
        return "fwd"
    return None


class ProgramSpans:
    """What the readers are given. Times in seconds unless `_ns`."""

    def __init__(self) -> None:
        self.window_s = 0.0
        self.spans: List[Span] = []            # cut to the window, nested
        self.devices: List[str] = []
        self.busy_total_s = 0.0                # summed over the devices
        self.module_s: Dict[str, float] = {}   # summed over the devices
        self.launches: Dict[str, float] = {}   # device 0; edges in part
        self.kernel_s: Dict[str, float] = {}   # by kernel, all devices
        self.kernel_launches: Dict[str, float] = {}
        self.phase_s: Dict[str, float] = {}    # fwd / bwd / opt / None
        self.idle_s = 0.0                      # device 0
        self.idle_in_engine_span_s = 0.0
        self.idle_gaps: List[Tuple[str, float]] = []   # ten longest

    # -- spans ----------------------------------------------------------

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def self_s_by_name(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.self_ns / 1e9
        return out

    def attribute_sums(self, name: str) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for s in self.named(name):
            for k, v in s.stats.items():
                if isinstance(v, int):
                    out[k] = out.get(k, 0) + v
        return out

    def tick_host_ms(self) -> Optional[float]:
        """Engine-thread time per `engine.tick` outside the spans in
        which it only waits for the device or for work."""
        ticks = self.named("engine.tick")
        if not ticks:
            return None
        total = sum(t.dur for t in ticks)
        for s in self.spans:
            if s.name in WAITS:
                p = s.parent
                while p is not None and p.name != "engine.tick":
                    p = p.parent
                if p is not None:
                    total -= s.dur
        return total / 1e6 / len(ticks)

    # -- device ---------------------------------------------------------

    def decode_steps(self) -> float:
        """Decode steps the device ran: k x launches of `jit_decode_k<k>`."""
        steps = 0.0
        for name, n in self.launches.items():
            m = DECODE_BLOCK.match(name)
            if m:
                steps += int(m.group(1)) * n
        return steps

    def decode_ms_step(self) -> Optional[float]:
        steps = self.decode_steps()
        if not steps or not self.devices:
            return None
        secs = sum(s for n, s in self.module_s.items()
                   if DECODE_BLOCK.match(n))
        return secs / len(self.devices) * 1e3 / steps

    def phase_pct(self, phase: str) -> Optional[float]:
        # Autodiff marks the backward pass in any program; only one that
        # names `fwd`, `loss_head` and `optimizer` has all three.
        if not self.busy_total_s or not all(self.phase_s.get(p)
                                            for p in ("fwd", "bwd", "opt")):
            return None
        return 100.0 * self.phase_s.get(phase, 0.0) / self.busy_total_s

    def idle_named_pct(self) -> Optional[float]:
        if not self.idle_s or not self.named("engine.tick"):
            return None
        return 100.0 * self.idle_in_engine_span_s / self.idle_s

    def summary(self) -> Dict[str, Any]:
        names = sorted({s.name for s in self.spans})
        return {
            "window_s": self.window_s,
            "span_counts": {n: len(self.named(n)) for n in names},
            "span_self_s": self.self_s_by_name(),
            "span_attribute_sums": {n: self.attribute_sums(n)
                                    for n in names},
            "tick_host_ms": self.tick_host_ms(),
            "devices": len(self.devices),
            "busy_total_s": self.busy_total_s,
            "module_s": self.module_s, "module_launches": self.launches,
            "decode_steps": self.decode_steps(),
            "decode_ms_step": self.decode_ms_step(),
            "kernel_s": self.kernel_s,
            "kernel_launches": self.kernel_launches,
            "phase_s": {str(k): v for k, v in self.phase_s.items()},
            "idle_s": self.idle_s,
            "idle_in_engine_span_s": self.idle_in_engine_span_s,
            "idle_gaps": [[n, s] for n, s in self.idle_gaps],
        }


def _module_name(raw: str) -> str:
    return raw.split("(")[0]


def reduce_profile(raw: Dict[str, Any]) -> ProgramSpans:
    """Everything is cut to the `bench:window` span where the trace has
    one, else to the extent of what it holds."""
    ps = ProgramSpans()
    spans: List[Span] = list(raw.get("spans", []))
    devs = raw.get("devices", {})
    order = sorted(devs, key=lambda p: int(
        xplane.DEVICE_PLANE.match(p).group(1)))
    if raw.get("window"):
        t0, t1 = raw["window"]
    else:
        edges = [(s.start, s.end) for s in spans] + [
            (s, s + d) for p in order for _, s, d in devs[p]["ops"]]
        if not edges:
            return ps
        t0, t1 = min(e[0] for e in edges), max(e[1] for e in edges)
    ps.window_s = (t1 - t0) / 1e9
    for s in spans:
        if s.end <= t0 or s.start >= t1:
            continue
        start = max(s.start, t0)
        ps.spans.append(Span(s.name, start, min(s.end, t1) - start,
                             s.thread, s.stats))
    nest(ps.spans)

    scopes = raw.get("scopes", {})
    for i, p in enumerate(order):
        ops: List[Event] = []
        for name, s, d in devs[p]["ops"]:
            part = min(s + d, t1) - max(s, t0)
            if part <= 0:
                continue
            ops.append((name, max(s, t0), part))
            if KERNEL_CALL in name:
                m = KERNEL_NAME.search(name)
                if m:
                    k = m.group(1)
                    ps.kernel_s[k] = ps.kernel_s.get(k, 0.0) + part / 1e9
                    ps.kernel_launches[k] = ps.kernel_launches.get(
                        k, 0.0) + part / d
            if not xplane.CONTAINER.match(
                    xplane.op_name(name).split(" ")[0]):
                phase = phase_of(scopes.get(name))
                ps.phase_s[phase] = ps.phase_s.get(phase, 0.0) + part / 1e9
        if not ops:
            continue
        ps.devices.append(p)
        ps.busy_total_s += xplane.union_ns(
            [(s, s + d) for _, s, d in ops]) / 1e9
        for name, s, d in devs[p]["modules"]:
            part = min(s + d, t1) - max(s, t0)
            if part > 0:
                key = _module_name(name)
                ps.module_s[key] = ps.module_s.get(key, 0.0) + part / 1e9
        if len(ps.devices) == 1:
            ps.launches = _launches(devs[p]["modules"], t0, t1)
        if len(ps.devices) == 1:
            _idle(ps, ops, t0, t1)
    return ps


def _launches(modules: Sequence[Event], t0: float, t1: float
              ) -> Dict[str, float]:
    """Launches of each program in [t0, t1). One that lies wholly inside
    counts 1. One cut by an edge counts the part inside over a whole
    launch's length, and that length is not its event's: the profiler
    starts and stops a little outside the window and records a program
    that was running then as beginning, or ending, there. So the length
    is the median of the program's whole launches in the window, else,
    for a decode block, k times the per-step time of the whole decode
    launches, else what the event says."""
    whole: Dict[str, List[float]] = {}
    edge: List[Tuple[str, float, float]] = []
    for raw, s, d in modules:
        part = min(s + d, t1) - max(s, t0)
        if part <= 0:
            continue
        name = _module_name(raw)
        if s >= t0 and s + d <= t1:
            whole.setdefault(name, []).append(d)
        else:
            edge.append((name, part, d))
    steps = sum(int(DECODE_BLOCK.match(n).group(1)) * len(ds)
                for n, ds in whole.items() if DECODE_BLOCK.match(n))
    step_ns = sum(sum(ds) for n, ds in whole.items()
                  if DECODE_BLOCK.match(n)) / steps if steps else 0.0
    out = {n: float(len(ds)) for n, ds in whole.items()}
    for name, part, d in edge:
        block = DECODE_BLOCK.match(name)
        if name in whole:
            full = statistics.median(whole[name])
        elif block and step_ns:
            full = int(block.group(1)) * step_ns
        else:
            full = d
        out[name] = out.get(name, 0.0) + min(1.0, part / full)
    return out


def flash_roofline_pct(m: Dict[str, Any], ps: ProgramSpans, backward: bool
                       ) -> Optional[float]:
    """One class of flash kernel against its roofline in training: the
    least time the chip could take for the launches that ran (the larger
    of operations over peak FLOP/s and bytes over peak bytes/s, from the
    shapes, `lib/stats.flash_flops_bytes`) over the time their events
    took. Forward: `flash_fwd`, the remat recomputation a launch like
    any other, on both sides of the ratio. Backward: `flash_dq` and
    `flash_dkv` together, a pair of them one backward pass."""
    if m["ctx"].rehearse or "batch_size" not in m or not ps.devices:
        return None
    names = ("flash_dq", "flash_dkv") if backward else ("flash_fwd",)
    spent = sum(ps.kernel_s.get(n, 0.0) for n in names)
    launches = sum(ps.kernel_launches.get(n, 0.0) for n in names) / len(names)
    if not spent or not launches:
        return None
    a = m["arch"]
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    # A launch works on one device's share of the batch.
    fb = stats.flash_flops_bytes(
        m["batch_size"] / len(ps.devices), a["n_heads"], a["n_kv_heads"],
        m["seq_len"], m["seq_len"], a["d_model"] // a["n_heads"],
        causal=True, backward=backward)
    least = max(fb["flops"] / peak["bf16_flops"],
                fb["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least * launches / spent


def _idle(ps: ProgramSpans, ops: Sequence[Event], t0: float, t1: float
          ) -> None:
    """Device 0's idle gaps: their sum, the part of it inside an
    `engine.*` span other than `engine.tick` itself, and the ten longest,
    each named by the innermost program span that covers most of it."""
    busy = xplane.merged([(s, s + d) for _, s, d in ops])
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    ps.idle_s = sum(e - s for s, e in gaps) / 1e9
    inner = xplane.merged([(s.start, s.end) for s in ps.spans
                           if s.name.startswith("engine.")
                           and s.name != "engine.tick"])
    ps.idle_in_engine_span_s = (
        sum(e - s for s, e in gaps)
        - xplane.subtract_ns(gaps, inner)) / 1e9
    gaps.sort(key=lambda g: g[0] - g[1])
    for gs, ge in gaps[:10]:
        best, rank = "no_program_span", (0.0, -1)
        for s in ps.spans:
            cover = min(ge, s.end) - max(gs, s.start)
            if cover > 0 and (cover, s.depth) > rank:
                best, rank = s.name, (cover, s.depth)
        ps.idle_gaps.append((best, (ge - gs) / 1e9))


# -- for the readers --------------------------------------------------------

def for_run(m: Dict[str, Any]) -> Optional[ProgramSpans]:
    """The reduction of this run's trace, made once for all the readers
    (they are handed the same `m`): also written to
    `.bench_out/<cell>/program_spans.json`, with the ten longest idle
    gaps by program span on an earlier output line."""
    if "program_spans" in m:
        return m["program_spans"]
    ctx = m["ctx"]
    path = xplane.find_xplane(os.path.join(ctx.out_dir, "trace")) \
        if ctx.trace else None
    ps = m["program_spans"] = \
        reduce_profile(read_profile(path)) if path else None
    if ps is not None:
        summary = ps.summary()
        with open(os.path.join(ctx.out_dir, "program_spans.json"), "w") as f:
            json.dump(summary, f, indent=1)
        ctx.log(phase="program_spans", idle_s=ps.idle_s,
                idle_in_engine_span_s=ps.idle_in_engine_span_s,
                idle_gaps_by_program_span=summary["idle_gaps"],
                decode_steps=summary["decode_steps"],
                span_counts=summary["span_counts"],
                kernel_s=ps.kernel_s, kernel_launches=ps.kernel_launches,
                phase_s=summary["phase_s"])
    return ps
