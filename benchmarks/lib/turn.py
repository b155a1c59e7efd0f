"""A turn between two programs, on one clock: what the engine's thread
asks of the device call by call (`ray_tpu:engine.device_call`, one span a
call, numbered), what each `engine.fetch` waited for (its `call`), and the
distance between the profile's two timelines, measured from the trace's
own causality. Reads the same `.xplane.pb` as `lib/reqpath.py` (its
`read_profile` lists, its `join`); neither is edited.

- **The clock.** A device program cannot begin before the host called it,
  and the host cannot hold a result before the device finished. Over the
  stretch's joined pairs `offset_hi = min(module start - call start)` (the
  program's own `engine.device_call`) and `offset_lo = max(module end -
  fetch end)` (the `engine.fetch` that names that call) bracket `device
  time - host time`. `offset_lo > offset_hi` says the join or a span is
  wrong and is reported, never clipped. Readings across the timelines use
  `offset_hi` (an idle device starts a program within tens of
  microseconds of its call): a device time moved to the host's clock by it
  reads *early* by at most the bracket's width, so a launch's distance to
  its program's start reads short and a result's way back long by that
  much.
- **The calls.** `engine.device_call` spans that begin in the stretch over
  the `engine.launch` spans there (`to_host` left out: it is the fetch);
  the length of every `op` under a tile and under a block; how much of
  `engine.launch` its children cover.
- **Eager programs.** An eager call has no name of its own on the device.
  The device runs in order, so the module events that carry none of the
  programs' names and lie between two named launches belong to the eager
  calls the thread made between those two; within such a group an event
  goes to the last of those calls that had begun when it started (on the
  aligned clock), which holds where the device is idle and is said where
  it is not.
- **A decode launch's fixed time.** Per `jit_decode_k<k>` event joined to
  its call (`k`): the event's length less the time of the loop's
  operations, told from the device's own events (see `launch_fixed`).

Two stages like its neighbours: `reduce_turn` turns `read_profile`'s
lists into a `Turn`; `for_run` does it once a run, writes
`.bench_out/<cell>/device_turn.json` and logs a `device_turn` line. On a
program without the spans (an older commit) every reader gives None.
"""

from __future__ import annotations

import bisect
import json
import os
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import progspans, reqpath, xplane
from .progspans import DECODE_BLOCK, PREFILL, Event, Span

CALL, FETCH, LAUNCH = "engine.device_call", "engine.fetch", "engine.launch"
EMIT, SUBMIT, TILE = reqpath.EMIT, reqpath.SUBMIT, reqpath.TILE
ADMIT, BLOCK = reqpath.ADMIT, reqpath.BLOCK
# Calls that run a program of jit's own naming on the device.
EAGER = ("split", "slice", "scatter", "pad", "stack", "concatenate")
# How close to the extreme a pair has to lie to count as setting an end.
NEAR_NS = 50e3
# A device that ran nothing for this long before a program began was idle
# when the program was launched (an eager program lasts 1-20 us).
IDLE_NS = 100e3
# How far before its call's start an eager program may seem to begin on
# the aligned clock: `offset_hi` holds the shortest way of a jitted
# program from its call to the device, and an eager one's is shorter
# (docqa: the key's split begins 24-208 us "before" its call; my chip
# run, PR 52). Less than any eager call lasts on the host (0.25 ms up).
EARLY_NS = 300e3
# The parts of a lone request's way to its first token, on one clock, in
# order; they add up to `submit_to_first_token`.
PARTS = ("submit_to_launch", "launch_to_call", "call_to_start", "tile_dev",
         "eager_dev", "way_back", "to_emit")
# What lies before a lone request's submit, where the stretch holds the
# block before its tile: that block's end on the device -> its fetch's end
# -> its emit's end -> the next request's submit (the tick's end, the
# engine's idle wait, the caller's poll and its next request).
BEFORE = ("block_way_back", "block_to_emit", "emit_to_submit")


def _median(vals: Sequence[float]) -> Optional[float]:
    return statistics.median(vals) if vals else None


def _ms(ns: Optional[float]) -> Optional[float]:
    return None if ns is None else ns / 1e6


# -- the clock ---------------------------------------------------------------

class Bracket:
    """`device time - host time` between `lo` and `hi`, ns."""

    def __init__(self) -> None:
        self.lo: Optional[float] = None
        self.hi: Optional[float] = None
        self.pairs_hi = self.pairs_lo = 0       # pairs looked at
        self.near_hi = self.near_lo = 0         # within NEAR_NS of the end
        self.hi_idle: Optional[bool] = None     # the launch that set `hi`
        self.quarters_hi: List[Optional[float]] = []

    @property
    def width(self) -> Optional[float]:
        if self.lo is None or self.hi is None:
            return None
        return self.hi - self.lo

    @property
    def crossed(self) -> bool:
        return self.width is not None and self.width < 0

    @property
    def drift(self) -> Optional[float]:
        """Largest less smallest `offset_hi` of the stretch's quarters."""
        got = [q for q in self.quarters_hi if q is not None]
        return max(got) - min(got) if len(got) > 1 else None

    def summary(self) -> Dict[str, Any]:
        return {"offset_lo_ms": _ms(self.lo), "offset_hi_ms": _ms(self.hi),
                "width_ms": _ms(self.width), "crossed": self.crossed,
                "pairs_hi": self.pairs_hi, "pairs_lo": self.pairs_lo,
                "pairs_near_hi": self.near_hi, "pairs_near_lo": self.near_lo,
                "hi_set_by_an_idle_launch": self.hi_idle,
                "offset_hi_by_quarter_ms": [_ms(q) for q in self.quarters_hi],
                "drift_ms": _ms(self.drift)}


def bracket(launched: Sequence[Tuple[float, float, bool]],
            fetched: Sequence[Tuple[float, float]],
            t0: float, t1: float) -> Bracket:
    """`launched`: (call start on the host, module start on the device,
    whether the device was idle when the module began) a joined program
    call; `fetched`: (fetch end on the host, module end on the device) a
    fetch whose call is joined. The stretch [t0, t1) is cut in four for
    the drift, read off the launches to an idle device."""
    b = Bracket()
    gaps = [(dev - host, idle) for host, dev, idle in launched]
    if gaps:
        b.hi, b.hi_idle = min(gaps)
        b.pairs_hi = len(gaps)
        b.near_hi = sum(g - b.hi <= NEAR_NS for g, _ in gaps)
        # A quarter's own `offset_hi`, from its launches to an idle device
        # alone: one that waited behind work says nothing of the clocks.
        quarter = (t1 - t0) / 4
        for q in range(4):
            part = [dev - host for host, dev, idle in launched
                    if idle and (t0 + q * quarter <= host
                                 < t0 + (q + 1) * quarter
                                 or (q == 3 and host >= t1))]
            b.quarters_hi.append(min(part) if part else None)
    lows = [dev - host for host, dev in fetched]
    if lows:
        b.lo = max(lows)
        b.pairs_lo = len(lows)
        b.near_lo = sum(b.lo - g <= NEAR_NS for g in lows)
    return b


# -- a decode launch's fixed time --------------------------------------------

def launch_fixed(ops: Dict[str, Sequence[float]], k: int, dur: float
                 ) -> Optional[Dict[str, Any]]:
    """One decode launch of `k` steps: `ops` is {operation: (events, ns,
    the first one's start, the last one's end, both from the module
    event's start)} of device 0's operations inside its module event,
    `dur` the event's length. The loop's operations are those whose events
    a launch are a multiple of k (the body of the loop over steps runs k
    times, a loop over layers inside it k x layers); the loop lasts from
    the first of them to the last. Fixed is the rest of the event: what
    lies before the loop and behind it (operations that run once a
    launch, an operation hoisted out of the loop whatever scope its
    metadata names, time in which nothing ran). What lies inside the
    loop's span is the loop's: the time between its own operations in
    which nothing ran grows with k (11 ns an event at mellum's sizes:
    0.22 ms of a block of 16, 0.43 of one of 32; `no_op_in_loop_ns`), and
    an operation there whose count is no multiple of k is a branch some
    steps take (`other_in_loop_ns`; a pass of block generation commits
    or denoises). Containers (`while`, `conditional`, `call`) hold other
    events and count nowhere. None for k < 2, where every count is a
    multiple, and for a launch no operation of which is one (an event cut
    by the trace's edge)."""
    if k < 2 or not ops:
        return None
    loop_ns = 0.0
    begin, end = float("inf"), float("-inf")
    once: List[Tuple[float, str, int, float, float]] = []
    for name, (n, ns, first, last) in ops.items():
        if xplane.CONTAINER.match(name.split(" ")[0]):
            continue
        if n >= k and n % k == 0:
            loop_ns += ns
            begin, end = min(begin, first), max(end, last)
        else:
            once.append((ns, name, int(n), first, last))
    if loop_ns == 0.0:
        return None
    outside = sorted((o for o in once if o[4] <= begin or o[3] >= end),
                     reverse=True)
    other = sum(o[0] for o in once) - sum(o[0] for o in outside)
    return {"k": k, "dur_ns": dur, "loop_ns": loop_ns,
            "fixed_ns": dur - (end - begin),
            "fixed_ops_ns": sum(o[0] for o in outside),
            "before_ns": begin, "behind_ns": dur - end,
            "other_in_loop_ns": other,
            "no_op_in_loop_ns": end - begin - other - loop_ns,
            "fixed_ops": [[name[:72], n, ns] for ns, name, n, _, _
                          in outside[:10]]}


def intercept(by_k: Dict[int, Sequence[float]]) -> Optional[float]:
    """Where the length of a launch of k steps meets k = 0: least squares
    through the median length a block size (two sizes: the line through
    both). None with fewer than two sizes."""
    pts = [(k, statistics.median(v)) for k, v in by_k.items() if v]
    if len(pts) < 2:
        return None
    n = len(pts)
    mk, md = sum(k for k, _ in pts) / n, sum(d for _, d in pts) / n
    slope = sum((k - mk) * (d - md) for k, d in pts) \
        / sum((k - mk) ** 2 for k, _ in pts)
    return md - slope * mk


def ops_by_launch(raw: Dict[str, Any], modules: Sequence[Event]
                  ) -> Dict[float, Dict[str, List[float]]]:
    """{a decode module event's start: {operation: [events, ns, the
    first one's start, the last one's end]}} for device 0, the two times
    from the module event's start. A recording (`checks/turn_trace.py`)
    carries it ready made under `decode_ops`; a profile's operations are
    walked once."""
    if "decode_ops" in raw:
        return {float(s): ops for s, ops in raw["decode_ops"].items()}
    ops, _ = reqpath._device0(raw)
    if not ops:
        return {}
    ops = sorted(ops, key=lambda o: o[1])
    starts = [o[1] for o in ops]
    out: Dict[float, Dict[str, List[float]]] = {}
    for name, s, d in modules:
        if not DECODE_BLOCK.match(progspans._module_name(name)):
            continue
        agg: Dict[str, List[float]] = {}
        for raw_name, os_, od in ops[bisect.bisect_left(starts, s):
                                     bisect.bisect_left(starts, s + d)]:
            a = agg.setdefault(xplane.op_name(raw_name),
                               [0, 0.0, os_ - s, 0.0])
            a[0] += 1
            a[1] += od
            a[3] = max(a[3], os_ + od - s)
        out[s] = agg
    return out


# -- the reduction -----------------------------------------------------------

class Turn:
    """What the readers are given. Times in ms unless named `_ns`."""

    def __init__(self) -> None:
        self.window_s = 0.0
        self.calls = 0              # `engine.device_call` spans, no to_host
        self.launches = 0           # `engine.launch` spans
        self.bracket = Bracket()
        self.join: Optional[Dict[str, Dict[str, int]]] = None
        self.result_latency: List[float] = []       # every waiting fetch
        self.result_latency_by: Dict[str, List[float]] = {}
        self.launch_to_start: List[float] = []      # idle launches only
        self.launch_to_start_all: List[float] = []
        self.tile_waits: List[float] = []           # aligned, every tile
        self.fixed: List[Dict[str, Any]] = []       # one a decode launch
        self.fixed_reason: Optional[str] = None
        self.decode_dur_by_k: Dict[int, List[float]] = {}
        self.by_op: Dict[str, Dict[str, Dict[str, Any]]] = {}
        self.launch_cover: Dict[str, float] = {}
        self.eager: Dict[str, Any] = {}
        self.eager_mode: Dict[str, int] = {}    # programs a call, by kind
        self.requests: List[Dict[str, Any]] = []
        self.client: Optional[Dict[str, Any]] = None

    # -- the readers --------------------------------------------------------

    def device_calls_per_launch(self) -> Optional[float]:
        if not self.calls or not self.launches:
            return None
        return self.calls / self.launches

    def result_latency_ms(self) -> Optional[float]:
        return _median(self.result_latency)

    def launch_to_start_ms(self) -> Optional[float]:
        return _median(self.launch_to_start)

    def decode_launch_fixed_ms(self) -> Optional[float]:
        return _median([f["fixed_ns"] / 1e6 for f in self.fixed])

    def decode_launch_intercept_ms(self) -> Optional[float]:
        return _ms(intercept(self.decode_dur_by_k))

    def median(self, part: str) -> Optional[float]:
        return _median([r[part] for r in self.requests])

    def summary(self) -> Dict[str, Any]:
        fixed_by_k: Dict[int, List[float]] = {}
        for f in self.fixed:
            fixed_by_k.setdefault(f["k"], []).append(f["fixed_ns"] / 1e6)
        longest = max(self.fixed, key=lambda f: f["k"], default=None)
        return {
            "window_s": self.window_s,
            "clock": self.bracket.summary(),
            "join": self.join,
            "device_calls": self.calls, "launches": self.launches,
            "device_calls_per_launch": self.device_calls_per_launch(),
            "result_latency_ms": {
                "median": self.result_latency_ms(),
                "fetches": len(self.result_latency),
                "by_program": {p: {"median": _median(v), "fetches": len(v)}
                               for p, v in self.result_latency_by.items()},
                "errs": "long by at most the clock's width"},
            "launch_to_start_ms": {
                "median_idle": self.launch_to_start_ms(),
                "idle_launches": len(self.launch_to_start),
                "median_all": _median(self.launch_to_start_all),
                "launches": len(self.launch_to_start_all),
                "errs": "short by at most the clock's width"},
            "tile_wait_aligned_ms": {
                "median": _median(self.tile_waits),
                "max": max(self.tile_waits, default=None),
                "tiles": len(self.tile_waits)},
            "decode_launch_fixed_ms": {
                "median": self.decode_launch_fixed_ms(),
                "launches": len(self.fixed), "none_because": self.fixed_reason,
                "by_k": {k: {"median": _median(v), "launches": len(v)}
                         for k, v in sorted(fixed_by_k.items())},
                "dur_ms_by_k": {k: _median(v) / 1e6 for k, v in
                                sorted(self.decode_dur_by_k.items()) if v},
                "intercept_ms": self.decode_launch_intercept_ms(),
                "of_the_longest": None if longest is None else {
                    "k": longest["k"], "dur_ms": longest["dur_ns"] / 1e6,
                    "fixed_ms": longest["fixed_ns"] / 1e6,
                    "fixed_operations_ms": longest["fixed_ops_ns"] / 1e6,
                    "before_the_loop_ms": longest["before_ns"] / 1e6,
                    "behind_the_loop_ms": longest["behind_ns"] / 1e6,
                    "no_operation_in_the_loop_ms":
                        longest["no_op_in_loop_ns"] / 1e6,
                    "ten_longest": [[n, c, ns / 1e6] for n, c, ns
                                    in longest["fixed_ops"]]}},
            "by_op": self.by_op,
            "launch_children_cover": self.launch_cover,
            "eager_programs": self.eager,
            "path_median_ms": {p: self.median(p) for p in PARTS
                               + ("submit_to_first_token",)},
            "before_submit_median_ms": {p: _median(
                [r[p] for r in self.requests if p in r]) for p in BEFORE},
            "requests_whole": len(self.requests),
            "client": self.client,
        }


def _kind(span: Span) -> Optional[str]:
    """`tile` or `block`: which launch or admission a call belongs to."""
    p = span
    while p is not None:
        if p.name == LAUNCH:
            return "block" if str(p.stats.get("program", "")).startswith(
                "decode_k") else "tile"
        if p.name == BLOCK:
            return "block"
        if p.name in (ADMIT, TILE, "engine.fuse_first"):
            return "tile"
        if p.name == FETCH:
            return "fetch"
        p = p.parent
    return None


def _busy_before(busy: Sequence[Tuple[float, float]], t: float) -> float:
    """How long device 0 had run nothing when `t` came (ns)."""
    i = bisect.bisect_left(busy, (t,)) - 1      # the last begun before t
    if i < 0:
        return float("inf")
    return max(0.0, t - busy[i][1])


def reduce_turn(raw: Dict[str, Any], log=None) -> Turn:
    tn = Turn()
    spans: List[Span] = list(raw.get("spans", []))
    if raw.get("window"):
        t0, t1 = raw["window"]
    elif spans:
        t0 = min(s.start for s in spans)
        t1 = max(s.end for s in spans)
    else:
        return tn
    tn.window_s = (t1 - t0) / 1e9
    engine = [s for s in spans if s.name != SUBMIT]
    progspans.nest(engine)
    inside = [s for s in engine if t0 <= s.start < t1]
    calls = sorted((s for s in inside if s.name == CALL
                    and isinstance(s.stats.get("call"), int)),
                   key=lambda s: s.stats["call"])
    launches = [s for s in inside if s.name == LAUNCH]
    if not calls or not launches:
        return tn
    tn.calls = sum(s.stats.get("op") != "to_host" for s in calls)
    tn.launches = len(launches)
    _lengths(tn, calls, launches, inside)

    ops, modules = reqpath._device0(raw)
    joined = reqpath.join(launches, modules, log) if modules else None
    if joined is None:
        return tn
    tn.join = {p: j.summary() for p, j in joined.items()}
    module_of = {(p, s.stats.get("seq")): m
                 for p, j in joined.items() for s, m in j.pairs}
    busy = xplane.merged([(s, s + d) for _, s, d in ops])

    # Program calls against their module events; fetches against theirs.
    programs = [c for c in calls if c.stats.get("op") == "program"]
    pairs = [(c, module_of[key]) for c in programs
             if (key := (str(c.stats.get("program")), c.stats.get("seq")))
             in module_of]
    by_call = {c.stats["call"]: c for c in calls}
    pair_of = {c.stats["call"]: m for c, m in pairs}
    fetches = [s for s in engine if s.name == FETCH
               and isinstance(s.stats.get("call"), int)
               and t0 <= s.end and s.start < t1]
    named = {"jit_" + str(c.stats.get("program")) for c in programs} \
        | {"jit_" + str(s.stats.get("program")) for s in launches}
    groups = _eager_groups(calls, pairs, modules, named)
    waited: List[Tuple[Span, float, str, bool]] = []
    for f in fetches:
        m = pair_of.get(f.stats["call"])
        if m is not None:
            waited.append((f, m[1] + m[2],
                           str(f.stats.get("program")), True))
            continue
        # An eager call's result: the programs before it, back to the
        # last named one, must have ended too.
        g = groups.get(f.stats["call"])
        if g is not None:
            waited.append((f, g["module"][1] + g["module"][2], "eager",
                           False))
    idle_for = {id(c): _busy_before(busy, m[1]) for c, m in pairs}
    tn.bracket = bracket(
        [(c.start, m[1], idle_for[id(c)] >= IDLE_NS) for c, m in pairs],
        [(f.end, end) for f, end, _, _ in waited], t0, t1)
    b = tn.bracket
    if b.crossed and log:
        log(phase="device_turn", clock_crossed=True, **b.summary())
    if b.hi is None:
        return tn
    hi = b.hi

    _assign_eager(tn, groups, hi)
    for f, end, program, exact in waited:
        if not exact:
            # The events of the calls up to the one waited for.
            end = _done_before(tn, groups[f.stats["call"]],
                               f.stats["call"], end)
        if f.start < end - hi:          # already waiting when it ended
            ms = (f.end - (end - hi)) / 1e6
            tn.result_latency.append(ms)
            tn.result_latency_by.setdefault(program, []).append(ms)
    for c, m in pairs:
        ms = (m[1] - hi - c.start) / 1e6
        tn.launch_to_start_all.append(ms)
        # The device had run nothing since before the call began (and
        # for IDLE_NS at the least): the program waited for nothing.
        if idle_for[id(c)] >= max(IDLE_NS, m[1] - hi - c.start):
            tn.launch_to_start.append(ms)
        if PREFILL.match("jit_" + str(c.stats.get("program"))):
            tn.tile_waits.append(ms)

    _fixed(tn, raw, pairs, modules)
    _requests(tn, spans, engine, by_call, pair_of, groups, hi, t0, t1)
    return tn


def _lengths(tn: Turn, calls: Sequence[Span], launches: Sequence[Span],
             inside: Sequence[Span]) -> None:
    """The length of every `op` a tile and a block, and the share of
    `engine.launch` its children cover."""
    parent_ns = {"tile": sum(s.dur for s in inside if s.name == ADMIT),
                 "block": sum(s.dur for s in launches if _kind(s) == "block")}
    units = {"tile": sum(s.name == TILE for s in inside),
             "block": sum(_kind(s) == "block" for s in launches)}
    for c in calls:
        kind = _kind(c)
        if kind is None:
            continue
        d = tn.by_op.setdefault(kind, {}).setdefault(
            str(c.stats.get("op")), {"durs": []})
        d["durs"].append(c.dur)
    for kind, ops in tn.by_op.items():
        for op, d in ops.items():
            durs = sorted(d.pop("durs"))
            d.update(calls=len(durs), median_ms=_median(durs) / 1e6,
                     p90_ms=durs[min(len(durs) - 1,
                                     int(0.9 * len(durs)))] / 1e6,
                     max_ms=durs[-1] / 1e6, total_ms=sum(durs) / 1e6)
            if units.get(kind):
                d["calls_a_" + kind] = len(durs) / units[kind]
            if parent_ns.get(kind):
                d["share_of_" + ("engine.admit" if kind == "tile"
                                 else "engine.launch")] = \
                    sum(durs) / parent_ns[kind]
    for kind in ("tile", "block", "all"):
        mine = [s for s in launches if kind in (_kind(s), "all")]
        total = sum(s.dur for s in mine)
        if total:
            ids = {id(s) for s in mine}
            tn.launch_cover[kind] = sum(
                c.dur for c in calls if id(c.parent) in ids) / total


def _eager_groups(calls: Sequence[Span],
                  pairs: Sequence[Tuple[Span, Event]],
                  modules: Sequence[Event], named: set
                  ) -> Dict[int, Dict[str, Any]]:
    """{an eager call's `call`: its group}: the eager calls the thread
    made between a joined program call and the next program call, and the
    module events with none of the programs' names between that program's
    event and the next named one."""
    order = [c.stats["call"] for c in calls]
    is_program = {c.stats["call"]: c.stats.get("op") == "program"
                  for c in calls}
    by_call = {c.stats["call"]: c for c in calls}
    starts = [m[1] for m in modules]
    out: Dict[int, Dict[str, Any]] = {}
    for c, m in pairs:
        i = bisect.bisect_right(order, c.stats["call"])
        mine = []
        while i < len(order) and not is_program[order[i]]:
            if by_call[order[i]].stats.get("op") in EAGER:
                mine.append(by_call[order[i]])
            i += 1
        closed = i < len(order)         # the next program call is there
        j = bisect.bisect_right(starts, m[1])
        events = []
        while j < len(modules) and progspans._module_name(
                modules[j][0]) not in named:
            events.append(modules[j])
            j += 1
        g = {"module": m, "program": c, "calls": mine, "events": events,
             "closed": closed and j < len(modules)}
        for e in mine:
            out[e.stats["call"]] = g
    return out


def _op_key(call: Span) -> str:
    """What makes two eager calls the same work: the `op`, and for a stack
    how many arrays it stacks (n reshapes and a concatenation)."""
    op = str(call.stats.get("op"))
    return f"{op}:{call.stats.get('n')}" if op == "stack" else op


def _assign_eager(tn: Turn, groups: Dict[int, Dict[str, Any]], hi: float
                  ) -> None:
    """Which of a group's events belong to which of its calls (`assigned`:
    [(call, event)]). First by time, where the device was idle: an event
    belongs to the last of the group's calls that had begun when it
    started on the aligned clock (and to none before its predecessor's).
    Where the events queue behind a program in flight they all start
    after the last call and time says nothing: there by count, if every
    call's kind was told apart by time somewhere in the stretch (its
    most frequent count of programs a call) and the counts add up to the
    group's events; else the group counts whole only."""
    per_op: Dict[str, Dict[str, List[float]]] = {}
    whole = {"groups": 0, "by_time": 0, "by_count": 0, "calls": 0,
             "events": 0, "event_ns": 0.0}
    todo, seen = [], set()
    for g in groups.values():
        if id(g) in seen or not g["closed"]:
            continue
        seen.add(id(g))
        mine, events = g["calls"], g["events"]
        whole["groups"] += 1
        whole["calls"] += len(mine)
        whole["events"] += len(events)
        whole["event_ns"] += sum(e[2] for e in events)
        if not mine or not events:
            continue
        if len(mine) > 1 and events[0][1] - hi + EARLY_NS >= mine[-1].start:
            todo.append(g)              # queued behind work in flight
            continue
        whole["by_time"] += 1
        starts = [c.start for c in mine]
        assigned, at = [], 0
        for e in events:
            at = max(at, bisect.bisect_right(
                starts, e[1] - hi + EARLY_NS) - 1, 0)
            assigned.append((mine[at], e))
        g["assigned"] = assigned
        for c in mine:
            got = [e for cc, e in assigned if cc is c]
            d = per_op.setdefault(_op_key(c), {"programs": [], "ns": [],
                                               "first": []})
            d["programs"].append(len(got))
            d["ns"].append(sum(e[2] for e in got))
            if got:
                d["first"].append(got[0][1] - hi - c.start)
    mode = tn.eager_mode = {
        key: max(set(d["programs"]), key=d["programs"].count)
        for key, d in per_op.items()}
    for g in todo:
        mine, events = g["calls"], g["events"]
        want = [mode.get(_op_key(c)) for c in mine]
        if None in want or sum(want) != len(events):
            continue
        whole["by_count"] += 1
        it = iter(events)
        g["assigned"] = [(c, next(it)) for c, n in zip(mine, want)
                         for _ in range(n)]
    n = whole["groups"]
    tn.eager = {
        "groups": n, "groups_told_by_time": whole["by_time"],
        "groups_told_by_count": whole["by_count"],
        "calls_a_group": whole["calls"] / n if n else None,
        "programs_a_group": whole["events"] / n if n else None,
        "device_us_a_group": whole["event_ns"] / 1e3 / n if n else None,
        "by_op": {key: {
            "calls": len(d["programs"]),
            "programs_a_call": mode[key],
            "calls_with_that_many": d["programs"].count(mode[key])
            / len(d["programs"]),
            "device_us_a_call_median": _median(d["ns"]) / 1e3,
            # Below zero: the aligned clock reads that much early there,
            # `offset_hi` lies that far above the truth.
            "first_program_after_call_ms_min":
                _ms(min(d["first"], default=None))}
            for key, d in sorted(per_op.items())}}


def _done_before(tn: Turn, g: Optional[Dict[str, Any]], call: int,
                 least: float) -> float:
    """When the device had finished what the eager call `call` of group
    `g` asked for: the end of the last event of the calls up to it.
    Where the group was not told apart, its events less those of the
    calls made later, by the count of programs such a call was seen to
    stand for; where that is not known either, `least` (the end of the
    named program before the group: no later than the truth)."""
    if g is None:
        return least
    if "assigned" in g:
        mine = [e for c, e in g["assigned"] if c.stats["call"] <= call]
    else:
        later = [tn.eager_mode.get(_op_key(c)) for c in g["calls"]
                 if c.stats["call"] > call]
        if None in later or sum(later) > len(g["events"]):
            return least
        mine = g["events"][:len(g["events"]) - sum(later)]
    return max([e[1] + e[2] for e in mine] + [least])


def _fixed(tn: Turn, raw: Dict[str, Any],
           pairs: Sequence[Tuple[Span, Event]], modules: Sequence[Event]
           ) -> None:
    blocks = [(c, m) for c, m in pairs
              if isinstance(c.stats.get("k"), int)
              and DECODE_BLOCK.match(progspans._module_name(m[0]))]
    if not blocks:
        tn.fixed_reason = "no decode block joined in the stretch"
        return
    by_start = ops_by_launch(raw, [m for _, m in blocks])
    for c, m in blocks:
        k = c.stats["k"]
        tn.decode_dur_by_k.setdefault(k, []).append(m[2])
        got = launch_fixed(by_start.get(m[1], {}), k, m[2])
        if got is not None:
            tn.fixed.append(got)
    if not tn.fixed:
        tn.fixed_reason = "no decode block of two steps or more joined " \
            "with its operations in the stretch"


def _requests(tn: Turn, spans: Sequence[Span], engine: Sequence[Span],
              by_call: Dict[int, Span], pair_of: Dict[int, Event],
              groups: Dict[int, Dict[str, Any]], hi: float, t0: float,
              t1: float) -> None:
    """A request's way to its first token on one clock: its submit, its
    tile's launch, the tile's own program call, the program's start and
    end on the device, the end of the eager programs its token went
    through, the fetch's end, the emit's end."""
    submits = {s.stats.get("req"): s for s in spans
               if s.name == SUBMIT and t0 <= s.start < t1}
    tiles = sorted((s for s in engine if s.name == TILE),
                   key=lambda s: s.start)
    firsts = sorted((s for s in engine if s.name == EMIT
                     and s.stats.get("first") == 1), key=lambda s: s.start)
    fetch_of = {}
    block_fetches = []      # (fetch, its program's end, its block's emit)
    for f in engine:
        if f.name != FETCH or f.parent is None:
            continue
        if f.parent.name == "engine.deliver_first":
            fetch_of[id(f.parent)] = f
        elif f.stats.get("call") in pair_of:
            emitted = next((e for e in engine if e.name == EMIT
                            and e.parent is f.parent), None)
            if emitted is not None:
                mod = pair_of[f.stats["call"]]
                block_fetches.append((f, mod[1] + mod[2], emitted))
    for rid, sub in sorted(submits.items(), key=lambda kv: kv[1].start):
        tile = next((t for t in tiles if str(rid) in reqpath._ids(t)
                     and t.start >= sub.start), None)
        emit = next((e for e in firsts if str(rid) in reqpath._ids(e)
                     and e.start >= sub.start), None)
        if tile is None or emit is None or emit.parent is None:
            continue
        launch = next((s for s in engine if s.name == LAUNCH
                       and s.parent is tile), None)
        pcall = next((c for c in by_call.values() if c.parent is launch
                      and c.stats.get("op") == "program"), None) \
            if launch is not None else None
        fetch = fetch_of.get(id(emit.parent))
        m = pair_of.get(pcall.stats["call"]) if pcall is not None else None
        if m is None or fetch is None \
                or not isinstance(fetch.stats.get("call"), int):
            continue
        done = _done_before(tn, groups.get(fetch.stats["call"]),
                            fetch.stats["call"], m[1] + m[2])
        before = {}
        last = max((f for f in block_fetches if f[0].end <= sub.start),
                   key=lambda f: f[0].end, default=None)
        if last is not None:
            f, ended, emitted = last
            before = {"block_way_back": (f.end - (ended - hi)) / 1e6,
                      "block_to_emit": (emitted.end - f.end) / 1e6,
                      "emit_to_submit": (sub.start - emitted.end) / 1e6}
        tn.requests.append({
            **before,
            "req": rid,
            "submit_to_launch": (launch.start - sub.start) / 1e6,
            "launch_to_call": (pcall.start - launch.start) / 1e6,
            "call_to_start": (m[1] - hi - pcall.start) / 1e6,
            "tile_dev": m[2] / 1e6,
            "eager_dev": (done - m[1] - m[2]) / 1e6,
            "way_back": (fetch.end - (done - hi)) / 1e6,
            "to_emit": (emit.end - fetch.end) / 1e6,
            "submit_to_first_token": (emit.end - sub.start) / 1e6})


def client_side(tn: Turn, rows: Sequence[Any]) -> None:
    """As `reqpath.client_side`: the client's median TTFT against the
    medians of the parts on one clock plus the hand-over."""
    by_id = {getattr(r.req, "id", None): r for r in rows}
    seen = [(p, by_id[p["req"]]) for p in tn.requests
            if p["req"] in by_id and by_id[p["req"]].first
            and by_id[p["req"]].req.first_token_ts]
    if not seen:
        return
    ttft = statistics.median((r.first - r.due) * 1e3 for _, r in seen)
    handoff = statistics.median(
        (r.first - r.req.first_token_ts) * 1e3 for _, r in seen)
    parts = sum(statistics.median(p[k] for p, _ in seen)
                for k in PARTS) + handoff
    tn.client = {"requests": len(seen), "ttft_median_ms": ttft,
                 "handoff_median_ms": handoff,
                 "parts_and_handoff_ms": parts,
                 "ttft_less_parts_ms": ttft - parts}


def for_run(m: Dict[str, Any]) -> Optional[Turn]:
    """The reduction of this run's trace, made once for the readers that
    share `m` (and the profile read once for `lib/reqpath.py` too):
    written to `.bench_out/<cell>/device_turn.json` and logged as the
    `device_turn` line where the program has the spans. Whatever fails
    here is logged if it can be and ends no run: the readers then read
    nothing."""
    if "device_turn" in m:
        return m["device_turn"]
    tn = m["device_turn"] = None
    ctx = m["ctx"]
    log = getattr(ctx, "log", None) or (lambda **kv: None)
    try:
        path = xplane.find_xplane(os.path.join(ctx.out_dir, "trace")) \
            if ctx.trace else None
        if not path:
            return None
        if "raw_profile" not in m:
            m["raw_profile"] = progspans.read_profile(path)
        tn = reduce_turn(m["raw_profile"], log)
        client_side(tn, m.get("all_rows", ()))
        if tn.calls:
            summary = tn.summary()
            with open(os.path.join(ctx.out_dir, "device_turn.json"),
                      "w") as f:
                json.dump(dict(summary, requests=tn.requests,
                               fixed=tn.fixed), f, indent=1)
            log(phase="device_turn", **summary)
    except Exception as e:  # noqa: BLE001 — a reader never ends a run
        log(phase="device_turn", error=f"{type(e).__name__}: {e}")
        tn = None
    m["device_turn"] = tn
    return tn
