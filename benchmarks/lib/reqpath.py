"""A request's path and a tick's parts, from the same `.xplane.pb` as
`lib/progspans.py` reads (its `read_profile`). The file holds two
timelines: the host's (the caller's `engine.submit`, the engine thread's
spans) and the device's (module events, operations). The profiler puts
the second 0.7 to 1.4 ms ahead of the first, another distance a session
(PERF.md, section 6, PR 36), so a metric here is a difference within one
timeline; only the logged lines set a host time against a device time.

- The join: the n-th `ray_tpu:engine.launch` span of a program is the
  n-th event of its module (`jit_<program>`) on device 0. `join` pairs
  them in order and holds the pairing to what must be true of it: `seq`
  runs without a hole, and no module event begins before its span.
- A request's path, by its id: `engine.submit` -> the `engine.launch`
  inside the `engine.prefill_tile` whose `req_ids` hold it -> the
  `engine.emit` (`first=1`) whose `req_ids` hold it, all on the host's
  timeline, less the length of that launch's module event: what the
  host adds around a tile.
- The device's idle time between two requests: from the last decode
  block before a prefill tile to that tile, on the device's timeline,
  by the program span the engine's thread was in.
- What the host pays per unit, from the engine thread's CPU time
  (`cpu_us`, `ray_tpu/util/tracing.span(cpu=True)`): a decode step, a
  tile's admit. Logged, not metrics: a block's blocked call, a token's
  emit (the chip machine's thread clock steps by 10 ms).
- The ten longest idle gaps of device 0, each with the innermost program
  span and the launch the device was waiting for.

Two stages, like its neighbours: `reduce_paths` turns `read_profile`'s
plain lists into a `RequestPaths`; `for_run` does it once a run. On a
program without these spans (an older commit) every accessor gives None.
"""

from __future__ import annotations

import bisect
import json
import os
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import progspans, xplane
from .progspans import DECODE_BLOCK, PREFILL, Event, Span

SUBMIT, LAUNCH, EMIT = "engine.submit", "engine.launch", "engine.emit"
TICK, TILE, ADMIT = "engine.tick", "engine.prefill_tile", "engine.admit"
BLOCK = "engine.dispatch_block"
# A request's way to its first token, in order; they add up to
# `submit_to_first_token`.
PARTS = ("submit_to_launch", "tile_dev", "first_token_overhead")
# Launches begun before the stretch whose programs start inside it: how
# many module events the pairing may skip at the front.
MAX_SHIFT = 16
# How far before its span a module event may begin and still be its
# launch's: more than the device's timeline runs ahead of the host's
# (a key's split shows on the device before the caller's `engine.submit`
# that caused it), less than any program lasts that the engine launches
# twice in a row. A lone caller's tile begins within half a millisecond
# of its call.
CLOCK_SLACK_NS = 2e6


class Joined:
    """One program's launches in the stretch against its module events."""

    def __init__(self) -> None:
        self.pairs: List[Tuple[Span, Event]] = []
        self.launches = 0       # spans that begin in the stretch
        self.skipped = 0        # module events of earlier launches
        self.cut = 0            # spans whose program the trace ends before

    def summary(self) -> Dict[str, int]:
        return {"launches": self.launches, "joined": len(self.pairs),
                "skipped_modules": self.skipped, "cut_by_the_end": self.cut}


def join(launches: Sequence[Span], modules: Sequence[Event],
         log=None) -> Optional[Dict[str, Joined]]:
    """{program: Joined} for the `engine.launch` spans given (those that
    begin in the stretch) and device 0's module events. None where the
    spans' `seq` has a hole or no pairing keeps every module event behind
    its span (less `CLOCK_SLACK_NS`); `log(...)` is told how many could
    not be matched."""
    by_program: Dict[str, List[Span]] = {}
    for s in sorted(launches, key=lambda s: s.start):
        by_program.setdefault(str(s.stats.get("program")), []).append(s)
    out: Dict[str, Joined] = {}
    for program, spans in by_program.items():
        j = out[program] = Joined()
        j.launches = len(spans)
        seqs = [s.stats.get("seq") for s in spans]
        holes = sum(b != a + 1 for a, b in zip(seqs, seqs[1:])) \
            if all(isinstance(q, int) for q in seqs) else len(seqs)
        if holes:
            if log:
                log(phase="request_path", join_failed=program,
                    seq_holes=holes, launches=len(spans))
            return None
        mods = sorted((m for m in modules
                       if progspans._module_name(m[0]) == "jit_" + program
                       and m[1] >= spans[0].start - CLOCK_SLACK_NS),
                      key=lambda m: m[1])
        # The first pairing, shifting past the module events of launches
        # from before the stretch, in which every program starts after
        # its call does.
        for shift in range(min(MAX_SHIFT, len(mods)) + 1):
            pairs = list(zip(spans, mods[shift:]))
            early = sum(m[1] < s.start - CLOCK_SLACK_NS for s, m in pairs)
            if not early:
                j.pairs, j.skipped = pairs, shift
                j.cut = len(spans) - len(pairs)
                break
        else:
            if log:
                log(phase="request_path", join_failed=program,
                    modules_before_their_span=early, launches=len(spans),
                    modules=len(mods))
            return None
    return out


def _ids(span: Span) -> List[str]:
    return str(span.stats.get("req_ids", "")).split()


def _median(vals: Sequence[float]) -> Optional[float]:
    return statistics.median(vals) if vals else None


class RequestPaths:
    """What the readers are given. Times in ms unless named otherwise."""

    def __init__(self) -> None:
        self.window_s = 0.0
        self.joined: Optional[Dict[str, Joined]] = None
        # One dict a request submitted in the stretch whose chain is
        # whole: req, submit_to_launch, tile_dev, first_token_overhead
        # (and their sum, submit_to_first_token).
        self.requests: List[Dict[str, Any]] = []
        self.requests_submitted = 0
        # Launch start -> module start of every joined tile: across the
        # two timelines, so logged only, for the closed cells, where a
        # tile waits out the block in flight (4 to 390 ms).
        self.tile_waits: List[float] = []
        # Device 0's idle time from the last decode block before a tile
        # to the tile, one a tile, and the same time by program span.
        self.request_gaps: List[float] = []
        self.request_gap_by_span: Dict[str, float] = {}
        self.host: Dict[str, float] = {}      # sums over the whole ticks
        # The least (module start - its launch span's end) over the
        # joined pairs: how far below zero says how far the profile's
        # device timeline runs ahead of its host one (a program cannot
        # begin long before its call returns).
        self.device_began_to_call_end_ms: Optional[float] = None
        self.client: Optional[Dict[str, Any]] = None  # `client_side`
        self.idle_gaps: List[Dict[str, Any]] = []

    def median(self, part: str) -> Optional[float]:
        return _median([r[part] for r in self.requests])

    def request_gap_idle(self) -> Optional[float]:
        return _median(self.request_gaps)

    def _per(self, total: str, units: str, scale: float) -> Optional[float]:
        h = self.host
        if not h.get(units) or total not in h:
            return None
        return h[total] / h[units] * scale

    def host_cpu_ms_step(self) -> Optional[float]:
        return self._per("tick_cpu_us", "steps", 1e-3)

    def admit_host_ms_tile(self) -> Optional[float]:
        return self._per("admit_cpu_us", "admit_tiles", 1e-3)

    def summary(self) -> Dict[str, Any]:
        parts = PARTS + ("submit_to_first_token",)
        return {
            "window_s": self.window_s,
            "join": {p: j.summary() for p, j in self.joined.items()}
            if self.joined is not None else None,
            "requests_submitted": self.requests_submitted,
            "requests_whole": len(self.requests),
            "path_median_ms": {p: self.median(p) for p in parts},
            "client": self.client,
            "device_began_to_call_end_ms_min":
                self.device_began_to_call_end_ms,
            "tile_wait_median_ms": _median(self.tile_waits),
            "tiles_joined": len(self.tile_waits),
            "request_gap_idle_ms": {
                "median": self.request_gap_idle(),
                "mean": statistics.fmean(self.request_gaps)
                if self.request_gaps else None,
                "max": max(self.request_gaps, default=None),
                "gaps": len(self.request_gaps),
                "mean_by_span": {
                    k: v / len(self.request_gaps)
                    for k, v in self.request_gap_by_span.items()}},
            "host": dict(self.host),
            "host_cpu_ms_step": self.host_cpu_ms_step(),
            "admit_host_ms_tile": self.admit_host_ms_tile(),
            # Too fine for a thread clock of 10 ms steps, or as uneven as
            # the blocks they wait behind: to read, not to compare.
            "launch_blocked_ms_block": self._per(
                "decode_launch_blocked_us", "decode_launches", 1e-3),
            "emit_host_us_token": self._per(
                "emit_cpu_us", "emit_tokens", 1.0),
            "idle_gaps": self.idle_gaps,
        }


def _tick_of(span: Span) -> Optional[Span]:
    p = span
    while p is not None and p.name != TICK:
        p = p.parent
    return p


def _host_costs(engine: Sequence[Span], t0: float, t1: float
                ) -> Dict[str, float]:
    """Sums over the ticks that begin in the stretch and what lies inside
    them: a span's `cpu_us` is of the whole span, so a tick counts whole
    or not at all, and its blocks, emits and admits with it."""
    inside = [s for s in engine if (t := _tick_of(s)) is not None
              and t0 <= t.start < t1 and "cpu_us" in t.stats]
    h: Dict[str, float] = {}

    def add(key: str, v: float) -> None:
        h[key] = h.get(key, 0.0) + v

    for s in inside:
        cpu = s.stats.get("cpu_us")
        if s.name == TICK:
            add("ticks", 1)
            add("tick_cpu_us", cpu)
            add("tick_us", s.dur / 1e3)
        elif s.name == BLOCK:
            add("steps", s.stats.get("k", 0))
            add("dispatch_block_us", s.dur / 1e3)
        elif s.name == LAUNCH and cpu is not None and str(
                s.stats.get("program", "")).startswith("decode_k"):
            add("decode_launches", 1)
            add("decode_launch_us", s.dur / 1e3)
            add("decode_launch_blocked_us", s.dur / 1e3 - cpu)
        elif s.name == EMIT and cpu is not None:
            add("emit_cpu_us", cpu)
            add("emit_us", s.dur / 1e3)
            add("emit_tokens", s.stats.get("tokens", 0))
        elif s.name == ADMIT and cpu is not None:
            add("admit_cpu_us", cpu)
            add("admit_us", s.dur / 1e3)
        elif s.name == TILE and s.parent is not None \
                and s.parent.name == ADMIT:
            add("admit_tiles", 1)
    return h


def _device0(raw: Dict[str, Any]) -> Tuple[List[Event], List[Event]]:
    devs = raw.get("devices", {})
    for p in sorted(devs, key=lambda p: int(
            xplane.DEVICE_PLANE.match(p).group(1))):
        if devs[p]["ops"]:
            return devs[p]["ops"], sorted(
                (tuple(m) for m in devs[p]["modules"]), key=lambda m: m[1])
    return [], []


def _gaps(busy: Sequence[Tuple[float, float]], t0: float, t1: float
          ) -> List[Tuple[float, float]]:
    """The parts of [t0, t1) that no interval of `busy` (merged, in
    order) covers."""
    gaps, cur = [], t0
    for s, e in busy[max(0, bisect.bisect_left(busy, (t0,)) - 1):]:
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if t1 > cur:
        gaps.append((cur, t1))
    return [(s, e) for s, e in gaps if e > s]


def _innermost_ms(spans: Sequence[Span], t0: float, t1: float
                  ) -> Dict[str, float]:
    """[t0, t1) by the innermost of `spans` (nested) at each instant:
    {span name: ms}, `no_program_span` where none covers it."""
    over = [s for s in spans if s.end > t0 and s.start < t1]
    edges = sorted({t0, t1, *(min(max(e, t0), t1) for s in over
                              for e in (s.start, s.end))})
    out: Dict[str, float] = {}
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        inner = max((s for s in over if s.start <= mid < s.end),
                    key=lambda s: s.depth, default=None)
        name = inner.name if inner is not None else "no_program_span"
        out[name] = out.get(name, 0.0) + (b - a) / 1e6
    return out


def reduce_paths(raw: Dict[str, Any], log=None) -> RequestPaths:
    rp = RequestPaths()
    spans: List[Span] = list(raw.get("spans", []))
    if raw.get("window"):
        t0, t1 = raw["window"]
    elif spans:
        t0 = min(s.start for s in spans)
        t1 = max(s.end for s in spans)
    else:
        return rp
    rp.window_s = (t1 - t0) / 1e9
    # `engine.submit` is the caller's span, every other one the engine
    # thread's, and only those nest (taken apart by name: a thread that
    # did not name itself shows under its process's name, as the
    # caller's does).
    submits = {s.stats.get("req"): s for s in spans
               if s.name == SUBMIT and t0 <= s.start < t1}
    engine = [s for s in spans if s.name != SUBMIT]
    progspans.nest(engine)
    rp.requests_submitted = len(submits)
    rp.host = _host_costs(engine, t0, t1)

    ops, modules = _device0(raw)
    launches = [s for s in engine if s.name == LAUNCH
                and t0 <= s.start < t1]
    if launches and modules:
        rp.joined = join(launches, modules, log)
    module_of = {id(s): m for j in (rp.joined or {}).values()
                 for s, m in j.pairs}
    launch_of = {m: s for j in (rp.joined or {}).values()
                 for s, m in j.pairs}
    if launch_of:
        rp.device_began_to_call_end_ms = min(
            (m[1] - s.end) / 1e6 for m, s in launch_of.items())

    # Tiles and first tokens, by request.
    tile_launch = {id(s.parent): s for s in launches
                   if s.parent is not None and s.parent.name == TILE}
    tiles = sorted((s for s in engine if s.name == TILE),
                   key=lambda s: s.start)
    firsts = sorted((s for s in engine if s.name == EMIT
                     and s.stats.get("first") == 1), key=lambda s: s.start)
    for tile in tiles:
        launch = tile_launch.get(id(tile))
        m = module_of.get(id(launch)) if launch is not None else None
        if m is not None:
            rp.tile_waits.append((m[1] - launch.start) / 1e6)
    for rid, sub in sorted(submits.items(), key=lambda kv: kv[1].start):
        tile = next((t for t in tiles if str(rid) in _ids(t)
                     and t.start >= sub.start), None)
        launch = tile_launch.get(id(tile)) if tile is not None else None
        m = module_of.get(id(launch)) if launch is not None else None
        emit = next((e for e in firsts if str(rid) in _ids(e)
                     and e.start >= sub.start), None)
        if m is None or emit is None:
            continue
        # Host times against host times, and the module event's length:
        # no device time is set against a host one.
        rp.requests.append({
            "req": rid,
            "submit_to_launch": (launch.start - sub.start) / 1e6,
            "tile_dev": m[2] / 1e6,
            "first_token_overhead": (emit.end - launch.start - m[2]) / 1e6,
            "submit_to_first_token": (emit.end - sub.start) / 1e6})

    if not ops:
        return rp
    busy = xplane.merged([(s, s + d) for _, s, d in ops])
    # Idle time from the last decode block before a tile to the tile: a
    # lone caller's turn from one request to its next.
    decodes = [(s, s + d) for name, s, d in modules
                   if DECODE_BLOCK.match(progspans._module_name(name))]
    for name, s, _ in modules:
        i = bisect.bisect_left(decodes, (s,))
        if not i or not t0 <= s < t1 \
                or not PREFILL.match(progspans._module_name(name)):
            continue
        idle = _gaps(busy, min(decodes[i - 1][1], s), s)
        rp.request_gaps.append(sum(e - b for b, e in idle) / 1e6)
        for b, e in idle:
            for span, ms in _innermost_ms(engine, b, e).items():
                rp.request_gap_by_span[span] = \
                    rp.request_gap_by_span.get(span, 0.0) + ms
    rp.idle_gaps = _longest_gaps(_gaps(busy, t0, t1), engine, modules,
                                 launch_of)
    return rp


def _longest_gaps(gaps: Sequence[Tuple[float, float]], engine: Sequence[Span],
                  modules: Sequence[Event], launch_of: Dict[Event, Span]
                  ) -> List[Dict[str, Any]]:
    """The ten longest idle gaps: what the engine's thread was in, and
    the launch whose program the device was waiting for."""
    joined_modules = sorted(launch_of, key=lambda m: m[1])
    starts = [m[1] for m in joined_modules]
    out = []
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        by_span = _innermost_ms(engine, gs, ge)
        nxt = next((m for m in modules if m[1] >= gs), None)
        gap: Dict[str, Any] = {
            "idle_ms": (ge - gs) / 1e6,
            "span": max(by_span, key=by_span.get, default="no_program_span"),
            "by_span_ms": by_span,
            "next_module": progspans._module_name(nxt[0])
            if nxt is not None and nxt[1] <= ge else None,
            "awaited": None}
        # The first program with a launch span to begin once the gap
        # has: the unnamed programs before it (a key's split, a slice)
        # are a few microseconds of the same admission or dispatch.
        i = bisect.bisect_left(starts, gs)
        if i < len(starts):
            m = joined_modules[i]
            launch = launch_of[m]
            # Where the call began, from the gap's start: below zero,
            # it was pending all through the gap.
            gap["awaited"] = {
                "program": launch.stats.get("program"),
                "seq": launch.stats.get("seq"),
                "launch_began_ms_into_gap": (launch.start - gs) / 1e6,
                "module_began_ms_into_gap": (m[1] - gs) / 1e6,
                "launch_ms": launch.dur / 1e6}
        out.append(gap)
    return out


def client_side(rp: RequestPaths, rows: Sequence[Any]) -> None:
    """The stretch's whole requests as their caller saw them (the
    harness's rows: `due`, `first` on `time.monotonic`, the engine's
    `first_token_ts` on the request): median client TTFT against the
    medians of the path's parts plus the hand-over to the client, which
    together should be it."""
    by_id = {getattr(r.req, "id", None): r for r in rows}
    seen = [(p, by_id[p["req"]]) for p in rp.requests
            if p["req"] in by_id and by_id[p["req"]].first
            and by_id[p["req"]].req.first_token_ts]
    if not seen:
        return
    ttft = statistics.median((r.first - r.due) * 1e3 for _, r in seen)
    handoff = statistics.median(
        (r.first - r.req.first_token_ts) * 1e3 for _, r in seen)
    parts = sum(statistics.median(p[k] for p, _ in seen)
                for k in PARTS) + handoff
    rp.client = {"requests": len(seen), "ttft_median_ms": ttft,
                 "handoff_median_ms": handoff,
                 "parts_and_handoff_ms": parts,
                 "ttft_less_parts_ms": ttft - parts}


def for_run(m: Dict[str, Any]) -> Optional[RequestPaths]:
    """The reduction of this run's trace, made once for the readers that
    share `m`: also written to `.bench_out/<cell>/request_path.json`,
    with the medians, the host's costs and the ten longest idle gaps on
    an earlier output line."""
    if "request_path" in m:
        return m["request_path"]
    ctx = m["ctx"]
    path = xplane.find_xplane(os.path.join(ctx.out_dir, "trace")) \
        if ctx.trace else None
    rp = None
    if path:
        try:
            # Kept for whoever reads the profile next.
            if "raw_profile" not in m:
                m["raw_profile"] = progspans.read_profile(path)
            rp = reduce_paths(m["raw_profile"], ctx.log)
            client_side(rp, m.get("all_rows", ()))
        except Exception as e:  # noqa: BLE001 — a reader never ends a run
            ctx.log(phase="request_path", error=f"{type(e).__name__}: {e}")
    m["request_path"] = rp
    if rp is not None:
        summary = rp.summary()
        with open(os.path.join(ctx.out_dir, "request_path.json"), "w") as f:
            json.dump(dict(summary, requests=rp.requests), f, indent=1)
        ctx.log(phase="request_path", **summary)
    return rp
