"""A turn between two programs on one clock (`benchmarks/lib/turn.py`): the
bracket of the two timelines' distance on hand-made events (tight, loose,
crossed, no idle launch, drift), a decode launch's fixed time, the whole
reduction and each reader on hand-made events and on two stretches
recorded on the chip (docqa and batch, cut by
`benchmarks/checks/turn_trace.py`, kept beside this file), nothing on a
recording without the spans, and the six entries with their readers."""

import json
import os

import pytest

from checks import request_trace, turn_trace
from lib import turn
from lib.progspans import Span
from lib.spec import Spec

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MS = 1e6          # ns
ENG, CALLER = "llm-engine", "python3"

# Told by name, as `test_reqpath.py` tells its own: the next PR's entries
# go behind these (tests/conftest.py cuts `bench` behind the last).
ONLINE = ["engine.device_calls_per_launch.online",
          "engine.result_latency_ms.online",
          "engine.launch_to_start_ms.online",
          "model.decode_launch_fixed_ms.online"]
BATCH = ["engine.device_calls_per_launch.batch",
         "model.decode_launch_fixed_ms.batch"]
LONE_CELLS = ["mistral7b-docqa-lone", "mellum2-repoctx-lone"]
CLOSED_CELLS = ["internlm2-1b8-batch-closed", "trinity-mini-reason-closed",
                "openpangu-longgen-closed", "sdar-blockgen-closed",
                "glm5-longctx-closed", "solar-open2-rollout-closed",
                "jamba2-reason-wide-closed"]


# -- the clock ---------------------------------------------------------------

def _pairs(true_ms, launch_lags, fetch_lags, at=None):
    """Launches whose programs begin `launch_lags` ms after their calls
    and fetches that end `fetch_lags` ms after their programs, the
    device's timeline `true_ms` ahead of the host's (a number, or one a
    pair)."""
    n = len(launch_lags)
    at = at or [10.0 * i for i in range(n)]
    true = true_ms if isinstance(true_ms, list) else [true_ms] * max(
        n, len(fetch_lags))
    launched = [(t * MS, (t + lag + off) * MS, True)
                for t, lag, off in zip(at, launch_lags, true)]
    fetched = [((10.0 * i + 5 + lag) * MS, (10.0 * i + 5 + off) * MS)
               for i, (lag, off) in enumerate(zip(fetch_lags, true))]
    return launched, fetched


def test_a_tight_bracket_holds_the_true_distance():
    launched, fetched = _pairs(1.0, [0.4, 0.02, 0.3], [0.9, 0.05, 2.0])
    b = turn.bracket(launched, fetched, 0.0, 40 * MS)
    assert b.hi == pytest.approx(1.02 * MS) and b.lo == pytest.approx(
        0.95 * MS)
    assert b.lo <= 1.0 * MS <= b.hi and not b.crossed
    assert b.width == pytest.approx(0.07 * MS)
    assert (b.pairs_hi, b.pairs_lo, b.near_hi, b.near_lo) == (3, 3, 1, 1)
    got = b.summary()
    assert got["width_ms"] == pytest.approx(0.07) and not got["crossed"]
    assert got["hi_set_by_an_idle_launch"] is True


def test_a_loose_bracket_is_as_wide_as_its_shortest_lags():
    """Every launch waits out a block and every fetch finds its result
    late: the distance is held, loosely, and the width says so."""
    launched, fetched = _pairs(1.0, [20.0, 3.0, 35.0], [1.5, 2.5, 4.0])
    b = turn.bracket(launched, fetched, 0.0, 40 * MS)
    assert b.lo == pytest.approx(-0.5 * MS) and b.hi == pytest.approx(
        4.0 * MS)
    assert b.lo <= 1.0 * MS <= b.hi and b.width == pytest.approx(4.5 * MS)


def test_a_crossed_bracket_is_reported_and_not_clipped():
    """A program paired with the launch after its own begins `before` its
    call: the ends cross, and stay crossed."""
    launched, fetched = _pairs(1.0, [0.3, -2.0, 0.2], [0.5, 0.5, 0.5])
    b = turn.bracket(launched, fetched, 0.0, 40 * MS)
    assert b.hi == pytest.approx(-1.0 * MS) and b.lo == pytest.approx(
        0.5 * MS)
    assert b.crossed and b.width == pytest.approx(-1.5 * MS)
    assert b.summary()["crossed"] is True


def test_no_launch_to_an_idle_device_is_said():
    launched, fetched = _pairs(1.0, [7.0, 3.0], [0.5, 0.5])
    launched = [(h, d, False) for h, d, _ in launched]
    b = turn.bracket(launched, fetched, 0.0, 40 * MS)
    assert b.hi == pytest.approx(4.0 * MS) and b.hi_idle is False
    assert b.summary()["hi_set_by_an_idle_launch"] is False
    # Nothing joined: no bracket, and no reading across the timelines.
    empty = turn.bracket([], [], 0.0, 40 * MS)
    assert (empty.hi, empty.lo, empty.width, empty.drift) == (None,) * 4
    assert not empty.crossed and empty.quarters_hi == []


def test_drift_is_read_off_the_stretchs_four_quarters():
    """The device's timeline gains 0.1 ms a quarter on the host's."""
    at = [1.0, 11.0, 21.0, 31.0, 32.0]
    launched, fetched = _pairs([1.0, 1.1, 1.2, 1.3, 1.3],
                               [0.02] * 5, [0.5] * 5, at=at)
    b = turn.bracket(launched, fetched, 0.0, 40 * MS)
    assert [q / MS for q in b.quarters_hi] == pytest.approx(
        [1.02, 1.12, 1.22, 1.32])
    assert b.drift == pytest.approx(0.3 * MS)
    assert b.summary()["drift_ms"] == pytest.approx(0.3)
    # A quarter without a launch reads nothing and is left out.
    b = turn.bracket(launched[:1] + launched[3:], fetched, 0.0, 40 * MS)
    assert b.quarters_hi[1:3] == [None, None]
    assert b.drift == pytest.approx(0.3 * MS)


# -- a decode launch's fixed time --------------------------------------------

def _launch_ops(k, layers=3):
    """A block of k steps, times in ns from the launch's start: two
    fusions that run once (one of them hoisted out of the loop, under the
    loop's own scope for all its name says) and 100 us of nothing; the
    loop, 900 us of operations a step and 10 us a step between them in
    which nothing runs, one operation that one step alone runs; behind
    it three copies and 100 us of nothing. (events, ns, first start, last
    end) an operation."""
    t0 = 142e3
    t1 = t0 + k * 910e3
    ops = {"while.7": [1, k * 910e3, t0, t1],              # the container
           "fusion.11 bf16[4,4096]": [k * layers, k * 600e3, t0, t1 - 300e3],
           "fusion.12 bf16[4,14336]": [k * layers, k * 250e3, t0 + 200e3,
                                       t1 - 50e3],
           "fusion.90 s32[4]": [k, k * 50e3, t0 + 860e3, t1],
           "fusion.200 f32[4,32000]": [1, 30e3, 0.0, 30e3],
           "fusion.201 f32[4,32000]": [1, 12e3, 30e3, 42e3],
           "copy-done.3 s32[4]": [1, 5e3, t0 + 450e3, t0 + 455e3],
           "copy.5 bf16[4,4096]": [3, 8e3, t1, t1 + 8e3]}
    return ops, t1 + 8e3 + 100e3


@pytest.mark.parametrize("k", [2, 16, 32])
def test_a_launchs_fixed_time_is_what_does_not_grow_with_k(k):
    ops, dur = _launch_ops(k)
    got = turn.launch_fixed(ops, k, dur)
    assert got["k"] == k and got["loop_ns"] == pytest.approx(k * 900e3)
    # Before the loop 142 us and behind it 108: the same whatever k.
    assert got["fixed_ns"] == pytest.approx(250e3)
    assert (got["before_ns"], got["behind_ns"]) == pytest.approx(
        (142e3, 108e3))
    assert got["fixed_ops_ns"] == pytest.approx(50e3)
    # What lies inside the loop's span is the loop's: the time between
    # its operations, which grows with k, and what some step ran (the
    # container's time is its body's and counts nowhere).
    assert got["other_in_loop_ns"] == pytest.approx(5e3)
    assert got["no_op_in_loop_ns"] == pytest.approx(k * 10e3 - 5e3)
    assert [(n, c) for n, c, _ in got["fixed_ops"]] == [
        ("fusion.200 f32[4,32000]", 1), ("fusion.201 f32[4,32000]", 1),
        ("copy.5 bf16[4,4096]", 3)]


def test_a_one_step_block_and_a_launch_without_operations_read_nothing():
    assert turn.launch_fixed(*_launch_ops(1)[:1], 1, 1e6) is None
    assert turn.launch_fixed({}, 16, 1e6) is None
    # A launch cut by the trace's edge: no count is a multiple of k.
    ops, dur = _launch_ops(16)
    cut = {n: [c - 1, ns, a, b] for n, (c, ns, a, b) in ops.items() if c > 1}
    assert turn.launch_fixed(cut, 16, dur) is None


def test_the_intercept_of_length_on_k():
    by_k = {32: [114.90 * MS, 114.92 * MS, 114.88 * MS],
            16: [57.74 * MS], 64: []}
    # (16 x 114.90 - 32 x 57.74) / (16 - 32): the line through both.
    assert turn.intercept(by_k) == pytest.approx(0.58 * MS)
    by_k[64] = [229.36 * MS]
    assert 0.3 * MS < turn.intercept(by_k) < 0.7 * MS
    assert turn.intercept({32: [114.9 * MS]}) is None
    assert turn.intercept({}) is None


# -- the reduction, hand-made ------------------------------------------------

def _call(call, op, start_ms, dur_ms, **more):
    # (a microsecond short: two calls in a row do not touch)
    return Span("engine.device_call", start_ms * MS, dur_ms * MS - 1e3, ENG,
                dict(op=op, call=call, **more))


def _about(ms):
    return pytest.approx(ms, abs=5e-3)


def _raw(offset_ms=1.0, spans_too=True):
    """One lone request and the block behind its tile, the device's
    timeline `offset_ms` ahead of the host's. The caller submits at 5;
    the tile's program is called at 6.2 and begins 0.1 later (the device
    was idle), runs 20 ms; the scatters, the slice, the fusion's stack
    and concatenation and the block's split queue behind it and run when
    it ends, at 26.3; the first token's fetch returns at 27.3; the block
    of 4 steps is called at 7.85, begins when the split is done and runs
    40 ms; its fetch returns 1.6 ms after it ends. The request before:
    its last block ran from 0.4 to 2.4 and its fetch returned 0.5 ms
    later; that block's slice and this request's split met an idle
    device."""
    o = offset_ms
    spans = [
        Span("engine.submit", 5 * MS, 0.05 * MS, CALLER,
             {"req": 7, "prompt_tokens": 3000}),
        Span("engine.tick", 5.3 * MS, 22.3 * MS, ENG,
             {"tick": 3, "waiting": 1, "active": 0, "cpu_us": 2100}),
        Span("engine.admit", 5.4 * MS, 1.9 * MS, ENG,
             {"side": "slot", "taken": 1, "req_ids": "7", "cpu_us": 1500}),
        _call(100, "split", 5.5, 0.1),
        Span("engine.prefill_tile", 5.6 * MS, 1.4 * MS, ENG,
             {"side": "slot", "bucket": 4096, "rows": 1, "tile_rows": 1,
              "tokens": 3000, "req_ids": "7"}),
        Span("engine.tile_build", 5.7 * MS, 0.2 * MS, ENG, {}),
        Span("engine.launch", 6.0 * MS, 0.9 * MS, ENG,
             {"program": "prefill_sample_batch", "seq": 3, "cpu_us": 700}),
        _call(101, "to_device", 6.0, 0.2, n=4),
        _call(102, "program", 6.2, 0.7, program="prefill_sample_batch",
              seq=3),
        _call(103, "copy_start", 7.0, 0.02, n=1),
        _call(104, "to_device", 7.03, 0.02, n=1),
        _call(105, "scatter", 7.05, 0.05), _call(106, "scatter", 7.1, 0.05),
        _call(107, "slice", 7.15, 0.05),
        Span("engine.fuse_first", 7.3 * MS, 0.3 * MS, ENG, {"parts": 1}),
        _call(108, "stack", 7.3, 0.1, n=1),
        _call(109, "concatenate", 7.4, 0.1, n=1),
        _call(110, "copy_start", 7.5, 0.05, n=1),
        Span("engine.dispatch_block", 7.6 * MS, 0.8 * MS, ENG,
             {"block": 9, "k": 4, "active": 1, "slots": 4}),
        Span("engine.launch", 7.7 * MS, 0.6 * MS, ENG,
             {"program": "decode_k4", "seq": 10, "cpu_us": 300}),
        _call(111, "split", 7.7, 0.1), _call(112, "to_device", 7.8, 0.05,
                                             n=1),
        _call(113, "program", 7.85, 0.25, program="decode_k4", seq=10, k=4),
        _call(114, "slice", 8.1, 0.1), _call(115, "copy_start", 8.2, 0.05,
                                             n=2),
        Span("engine.deliver_first", 8.5 * MS, 19.1 * MS, ENG,
             {"tokens": 1}),
        Span("engine.fetch", 8.5 * MS, 18.8 * MS, ENG, {"call": 109}),
        _call(116, "to_host", 8.5, 18.75, n=1),
        _call(117, "to_host", 27.25, 0.03, n=1),
        Span("engine.emit", 27.3 * MS, 0.2 * MS, ENG,
             {"first": 1, "req_ids": "7", "tokens": 1, "finished": 0,
              "cpu_us": 250}),
        Span("engine.tick", 27.7 * MS, 41 * MS, ENG,
             {"tick": 4, "waiting": 0, "active": 1, "cpu_us": 900}),
        Span("engine.process_block", 28 * MS, 40.5 * MS, ENG,
             {"block": 9, "k": 4, "slots": 4, "active": 1, "emitted": 4,
              "discarded": 0}),
        Span("engine.fetch", 28 * MS, 40.0 * MS, ENG,
             {"call": 113, "program": "decode_k4", "seq": 10}),
        _call(118, "to_host", 28, 39.95, n=2),
        Span("engine.emit", 68.0 * MS, 0.2 * MS, ENG,
             {"tokens": 4, "finished": 1, "cpu_us": 160}),
        # The request before: its last block's slice, then, the device
        # idle, this request's split.
        Span("engine.launch", 0.2 * MS, 0.6 * MS, ENG,
             {"program": "decode_k4", "seq": 9, "cpu_us": 300}),
        _call(97, "program", 0.3, 0.19, program="decode_k4", seq=9, k=4),
        _call(98, "slice", 0.5, 0.09), _call(99, "copy_start", 0.6, 0.05,
                                             n=2),
        Span("engine.process_block", 0.85 * MS, 2.2 * MS, ENG,
             {"block": 8, "k": 4, "slots": 4, "active": 1, "emitted": 4,
              "discarded": 0}),
        Span("engine.fetch", 0.9 * MS, 2.0 * MS, ENG,
             {"call": 97, "program": "decode_k4", "seq": 9}),
        Span("engine.emit", 2.9 * MS, 0.1 * MS, ENG,
             {"tokens": 4, "finished": 1, "cpu_us": 90}),
    ]
    if not spans_too:
        spans = [s for s in spans if s.name != "engine.device_call"]
        for s in spans:
            s.stats.pop("call", None)
    modules = [
        ("jit_decode_k4(5)", (0.4 + o) * MS, 2.0 * MS),
        ("jit_dynamic_slice(9)", (2.41 + o) * MS, 0.003 * MS),
        ("jit__threefry_split(9)", (5.55 + o) * MS, 0.003 * MS),
        ("jit__unstack(9)", (5.57 + o) * MS, 0.002 * MS),
        ("jit_prefill_sample_batch(123)", (6.3 + o) * MS, 20 * MS),
        ("jit_scatter(4)", (26.31 + o) * MS, 0.005 * MS),
        ("jit_scatter(4)", (26.32 + o) * MS, 0.005 * MS),
        ("jit_dynamic_slice(9)", (26.33 + o) * MS, 0.003 * MS),
        ("jit_expand_dims(2)", (26.34 + o) * MS, 0.002 * MS),
        ("jit_concatenate(8)", (26.345 + o) * MS, 0.003 * MS),
        ("jit_concatenate(8)", (26.35 + o) * MS, 0.004 * MS),
        ("jit__threefry_split(9)", (26.36 + o) * MS, 0.003 * MS),
        ("jit__unstack(9)", (26.37 + o) * MS, 0.002 * MS),
        ("jit_decode_k4(5)", (26.4 + o) * MS, 40 * MS),
    ]
    ops = [("%fusion.1 = bf16[8] fusion()", s, d) for _, s, d in modules]
    # A block's operations: a loop of four steps over three layers, and
    # one fusion a launch (the earlier block is a short one's).
    k4 = {"while.3": [1, 39.5 * MS, 0.4 * MS, 39.9 * MS],
          "fusion.11 bf16[4,4096]": [12, 36 * MS, 0.4 * MS, 39 * MS],
          "fusion.90 s32[4]": [4, 3.5 * MS, 9 * MS, 39.9 * MS],
          "fusion.200 f32[4,32000]": [1, 0.3 * MS, 0.0, 0.3 * MS]}
    short = {"fusion.11 bf16[4,4096]": [12, 1.2 * MS, 0.3 * MS, 1.8 * MS],
             "fusion.90 s32[4]": [4, 0.4 * MS, 0.6 * MS, 1.9 * MS],
             "fusion.200 f32[4,32000]": [1, 0.3 * MS, 0.0, 0.3 * MS]}
    return {"spans": spans, "window": (0.0, 100 * MS), "scopes": {},
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "decode_ops": {(26.4 + o) * MS: k4, (0.4 + o) * MS: short}}


def test_the_bracket_of_a_hand_made_turn_holds_the_true_distance():
    tn = turn.reduce_turn(_raw(1.0))
    b = tn.bracket
    # The tile began 0.1 ms after its call (so did the block before it);
    # the tile's end is the latest the first token's fetch is known to
    # follow.
    assert b.hi == pytest.approx(1.1 * MS) and b.hi_idle is True
    # The earlier block's fetch returned 0.5 ms after its program ended:
    # the shortest way back sets the other end.
    assert b.lo == pytest.approx((2.4 + 1.0 - 2.9) * MS)
    assert b.lo <= 1.0 * MS <= b.hi and not b.crossed
    assert (b.pairs_hi, b.pairs_lo, b.near_hi, b.near_lo) == (3, 3, 2, 1)
    assert b.quarters_hi == [pytest.approx(1.1 * MS), None, None, None]
    # Another distance (the device's timeline ahead, as the chip's is;
    # within what `reqpath.join` takes for one launch's), the same
    # readings: they follow the measured one.
    far = turn.reduce_turn(_raw(-1.5))
    assert far.bracket.hi == pytest.approx(-1.4 * MS)
    assert far.bracket.lo == pytest.approx(-2.0 * MS)
    assert far.summary()["path_median_ms"] == pytest.approx(
        tn.summary()["path_median_ms"])
    assert far.result_latency == pytest.approx(tn.result_latency)


def test_the_calls_a_launch_and_the_length_of_every_op():
    tn = turn.reduce_turn(_raw())
    # 19 calls that are no `to_host` over three launches.
    assert (tn.calls, tn.launches) == (19, 3)
    assert tn.device_calls_per_launch() == pytest.approx(19 / 3)
    tile, block = tn.by_op["tile"], tn.by_op["block"]
    assert set(tile) == {"split", "to_device", "program", "copy_start",
                         "scatter", "slice", "stack", "concatenate"}
    assert tile["scatter"]["calls"] == 2
    assert tile["scatter"]["median_ms"] == _about(0.05)
    assert tile["scatter"]["share_of_engine.admit"] == _about(0.1 / 1.9)
    assert tile["program"]["calls_a_tile"] == 1
    assert block["program"]["calls"] == 2
    assert block["slice"]["total_ms"] == _about(0.19)
    assert block["slice"]["share_of_engine.launch"] == _about(0.19 / 1.2)
    assert tn.by_op["fetch"]["to_host"]["calls"] == 3
    # What of a launch its calls cover: the tile's transfers and program
    # all of it; a block's five (three of the earlier one's) 0.88 of 1.2.
    assert tn.launch_cover["tile"] == pytest.approx(1.0, abs=0.01)
    assert tn.launch_cover["block"] == pytest.approx(
        (0.55 + 0.33) / 1.2, abs=0.01)
    assert tn.launch_cover["all"] == pytest.approx(
        (0.9 + 0.88) / 2.1, abs=0.01)


def test_eager_programs_go_to_their_calls_by_time_and_then_by_count():
    tn = turn.reduce_turn(_raw())
    e = tn.eager
    # Two groups closed by a named launch on both sides. The slice and
    # the split met an idle device and are told apart by time: the
    # slice's one program ran when its block ended, the split's two when
    # the next request came (the first of them 0.05 ms `before` its call:
    # offset_hi lies that far above the truth, at the least). The six
    # calls queued behind the tile are not: a stack of one and a
    # concatenation were seen nowhere else, so the counts cannot add up.
    assert e["groups"] == 2 and e["groups_told_by_time"] == 1
    assert e["groups_told_by_count"] == 0
    assert e["by_op"]["split"]["programs_a_call"] == 2
    assert e["by_op"]["split"]["first_program_after_call_ms_min"] \
        == _about(-0.05)
    assert e["by_op"]["slice"]["programs_a_call"] == 1
    assert e["by_op"]["slice"]["calls_with_that_many"] == 1.0
    assert e["programs_a_group"] == pytest.approx((3 + 8) / 2)
    assert tn.eager_mode == {"slice": 1, "split": 2}


def test_a_results_way_back_and_a_launchs_way_out():
    tn = turn.reduce_turn(_raw())
    # The block's fetch: its program ends at 66.4 on the host's true
    # clock; moved by offset_hi (0.1 too far) it reads 66.3, and the
    # fetch ends at 68.0. The first token's: the events behind the tile
    # less the two a split was seen to stand for end with the
    # concatenation's, at 26.354 -> 26.254, and the fetch ends at 27.3.
    assert sorted(tn.result_latency) == pytest.approx([0.6, 1.046, 1.7])
    assert tn.result_latency_ms() == pytest.approx(1.046)
    assert sorted(tn.result_latency_by["decode_k4"]) == pytest.approx(
        [0.6, 1.7])
    assert tn.result_latency_by["eager"] == pytest.approx([1.046])
    # Launches to an idle device: the tile and the block before it (0 by
    # construction: they set offset_hi together); the block behind the
    # tile waited it out.
    assert tn.launch_to_start == pytest.approx([0.0, 0.0], abs=1e-9)
    assert sorted(tn.launch_to_start_all) == pytest.approx(
        [0.0, 0.0, 18.45], abs=1e-9)
    assert tn.tile_waits == pytest.approx([0.0], abs=1e-9)
    # A fetch that began when its result was there waited for nothing of
    # the way back: it is left out.
    raw = _raw()
    late = next(s for s in raw["spans"] if s.stats.get("call") == 113
                and s.name == "engine.fetch")
    late.start, late.dur = 67.5 * MS, 0.5 * MS
    assert sorted(turn.reduce_turn(raw).result_latency) == pytest.approx(
        [0.6, 1.046])


def test_a_lone_requests_way_adds_up_on_one_clock():
    tn = turn.reduce_turn(_raw())
    path, = tn.requests
    assert path["req"] == 7
    assert path["submit_to_launch"] == pytest.approx(1.0)
    assert path["launch_to_call"] == pytest.approx(0.2)
    assert path["call_to_start"] == pytest.approx(0.0, abs=1e-9)
    assert path["tile_dev"] == pytest.approx(20.0)
    # Two scatters, a slice, the stack's and the concatenation's programs.
    assert path["eager_dev"] == pytest.approx(0.054)
    assert path["way_back"] == pytest.approx(1.046)
    assert path["to_emit"] == pytest.approx(0.2)
    assert sum(path[p] for p in turn.PARTS) == pytest.approx(
        path["submit_to_first_token"]) == pytest.approx(22.5)
    # Before it: the last block's end (2.4 -> 2.3 on the aligned clock)
    # -> its fetch's end at 2.9 -> its emit's at 3.0 -> the submit at 5.
    assert [path[p] for p in turn.BEFORE] == pytest.approx([0.6, 0.1, 2.0])
    assert tn.summary()["before_submit_median_ms"]["emit_to_submit"] \
        == pytest.approx(2.0)


def test_the_fixed_time_of_the_stretchs_decode_launches():
    tn = turn.reduce_turn(_raw())
    assert [f["k"] for f in tn.fixed] == [4, 4]
    # 2 ms less the loop's 1.6 (from 0.3 to 1.9), and 40 less 39.5 (from
    # 0.4 to 39.9; the container counts nowhere): 0.3 of a fusion, the
    # rest no operation.
    assert sorted(f["fixed_ns"] / MS for f in tn.fixed) == pytest.approx(
        [0.4, 0.5])
    assert tn.decode_launch_fixed_ms() == pytest.approx(0.45)
    assert tn.decode_dur_by_k == {4: [2.0 * MS, 40 * MS]}
    assert tn.decode_launch_intercept_ms() is None      # one size
    got = tn.summary()["decode_launch_fixed_ms"]
    assert got["launches"] == 2 and got["by_k"][4]["launches"] == 2
    assert got["of_the_longest"]["ten_longest"] == [
        ["fusion.200 f32[4,32000]", 1, pytest.approx(0.3)]]


def test_a_program_without_the_spans_reads_as_nothing():
    said = []
    tn = turn.reduce_turn(_raw(spans_too=False), lambda **kv: said.append(kv))
    assert not said and (tn.calls, tn.launches) == (0, 0)
    for read in (tn.device_calls_per_launch, tn.result_latency_ms,
                 tn.launch_to_start_ms, tn.decode_launch_fixed_ms,
                 tn.decode_launch_intercept_ms):
        assert read() is None
    json.dumps(tn.summary())
    assert turn.reduce_turn({"spans": [], "devices": {}}).summary()[
        "device_calls_per_launch"] is None


# -- the reduction, recorded on the chip -------------------------------------

def _recorded(cell):
    path = os.path.join(HERE, f"recorded_turn_trace.{cell}.json.gz")
    assert os.path.getsize(path) < 250_000
    return turn_trace.load(path)


@pytest.mark.parametrize("cell", ["mistral7b-docqa-lone",
                                  "internlm2-1b8-batch-closed"])
def test_a_stretch_recorded_on_the_chip_reads_as_it_was_cut(cell):
    raw, kept = _recorded(cell)
    said = []
    tn = turn.reduce_turn(raw, lambda **kv: said.append(kv))
    assert not said
    got = json.loads(json.dumps(tn.summary()))
    want = kept["expect_turn"]
    assert got["clock"] == pytest.approx(want["clock"])
    assert got["join"] == want["join"]
    for key in ("device_calls_per_launch", "launch_children_cover",
                "path_median_ms"):
        assert got[key] == pytest.approx(want[key]), key
    for key, median in (("result_latency_ms", "median"),
                        ("launch_to_start_ms", "median_all"),
                        ("decode_launch_fixed_ms", "median")):
        assert got[key][median] == pytest.approx(want[key][median]), key
    # What the chip's trace looked like: the clock's ends do not cross
    # and lie within 3 ms of each other (no result reaches the thread in
    # under a millisecond and a half), a launch's calls cover it, and a
    # decode launch's fixed time is a few copies before its loop.
    b = tn.bracket
    assert b.lo <= b.hi and b.width < 3 * MS and b.hi_idle
    assert 3 < tn.device_calls_per_launch() < 12
    assert tn.launch_cover["all"] > 0.95
    assert all(cover > 0.9 for cover in tn.launch_cover.values())
    assert {"split", "to_device", "program", "slice", "copy_start"} \
        <= set(tn.by_op["block"])
    assert tn.fixed and all(0 < f["fixed_ns"] < 5 * MS for f in tn.fixed)
    assert all(f["before_ns"] > 10 * f["behind_ns"] for f in tn.fixed)
    assert all(f["fixed_ops"][0][0].startswith("copy")
               for f in tn.fixed)
    assert 0.0 < tn.decode_launch_fixed_ms() < 5.0


def test_a_docqa_stretch_recorded_on_the_chip_adds_up_on_one_clock():
    raw, _ = _recorded("mistral7b-docqa-lone")
    tn = turn.reduce_turn(raw)
    assert len(tn.requests) >= 3
    for r in tn.requests:
        assert sum(r[p] for p in turn.PARTS) == pytest.approx(
            r["submit_to_first_token"])
        assert r["tile_dev"] > 0.9 * r["submit_to_first_token"]
        assert 0 <= r["call_to_start"] < 1.0 and 0 < r["way_back"] < 5.0
    # A lone caller's tile finds the device idle; a result's way back is
    # a wait that had begun.
    assert len(tn.launch_to_start) >= 3
    assert 0 <= tn.launch_to_start_ms() < 1.0
    assert 0 < tn.result_latency_ms() < 5.0


@pytest.mark.parametrize("cell", ["mistral7b-docqa-lone",
                                  "internlm2-1b8-batch-closed"])
def test_a_recording_without_the_new_spans_reads_nothing(cell):
    """PR 36's recordings, cut on a program that had no
    `engine.device_call`: every reader gives None and none raises."""
    raw, _ = request_trace.load(os.path.join(
        HERE, f"recorded_request_trace.{cell}.json.gz"))
    tn = turn.reduce_turn(raw)
    assert tn.summary()["clock"]["offset_hi_ms"] is None
    for name in ONLINE + BATCH:
        spec = Spec(ROOT, LONE_CELLS[0] if name in ONLINE
                    else CLOSED_CELLS[0])
        reader = spec.load_module("layer_metrics", name)
        assert reader.read({"name": name}, {"device_turn": tn}) is None


# -- once a run --------------------------------------------------------------

class _Ctx:
    def __init__(self, out_dir, trace):
        self.out_dir, self.trace, self.said = str(out_dir), trace, []

    def log(self, **kv):
        self.said.append(kv)


def test_for_run_shares_the_profile_and_writes_the_line(tmp_path,
                                                        monkeypatch):
    reads = []
    monkeypatch.setattr(turn.xplane, "find_xplane", lambda d: d + "/x.pb")
    monkeypatch.setattr(turn.progspans, "read_profile",
                        lambda path: reads.append(path) or _raw())
    m = {"ctx": _Ctx(tmp_path, 1)}
    tn = turn.for_run(m)
    assert turn.for_run(m) is tn and len(reads) == 1
    assert m["raw_profile"]["window"] == (0.0, 100 * MS)
    with open(tmp_path / "device_turn.json") as f:
        kept = json.load(f)
    assert kept["requests"][0]["req"] == 7 and len(kept["fixed"]) == 2
    line, = m["ctx"].said
    assert line["phase"] == "device_turn"
    assert line["clock"]["offset_hi_ms"] == pytest.approx(1.1)
    assert line["device_calls_per_launch"] == pytest.approx(19 / 3)
    # The profile `lib/reqpath.py` read is not read again; no trace, no
    # reading; a reader's fault ends no run.
    m2 = {"ctx": _Ctx(tmp_path, 1), "raw_profile": _raw()}
    assert turn.for_run(m2).launches == 3 and len(reads) == 1
    off = {"ctx": _Ctx(tmp_path, 0)}
    assert turn.for_run(off) is None and off["ctx"].said == []
    bad = {"ctx": _Ctx(tmp_path, 1), "raw_profile": {"spans": [object()]}}
    assert turn.for_run(bad) is None
    assert bad["ctx"].said[0]["phase"] == "device_turn" \
        and "error" in bad["ctx"].said[0]


# -- the entries and their readers -------------------------------------------

def test_the_six_entries_are_the_last_of_per_layer(bench):
    last = bench["per_layer"][-6:]
    assert [m["name"] for m in last] == [
        ONLINE[0], BATCH[0], ONLINE[1], ONLINE[2], ONLINE[3], BATCH[1]]
    for m in last:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        online = m["name"] in ONLINE
        assert m["workloads"] == (LONE_CELLS if online else CLOSED_CELLS)
        assert m["better"] == "lower"
        assert m["layer"] == m["name"].split(".")[0].capitalize()
        fixed = "decode_launch_fixed" in m["name"]
        assert m["source"] == ("device_trace" if fixed else "program_span")
        assert m["unit"] == ("calls/launch" if "calls_per" in m["name"]
                             else "ms")
        assert m["moves"] == (
            "serve_out_tok_s" if not online
            else "tpot_p90_ms" if fixed else "ttft_p90_ms")


@pytest.mark.parametrize("cell", LONE_CELLS + CLOSED_CELLS)
def test_the_readers_load_for_every_cell_they_list(cell):
    spec = Spec(ROOT, cell)
    mine = [m for m in spec.metrics("per_layer")
            if m["name"] in ONLINE + BATCH]
    assert sorted(m["name"] for m in mine) == sorted(
        ONLINE if cell in LONE_CELLS else BATCH)
    moved = {m["name"] for m in spec.metrics("end_to_end")}
    for m in mine:
        assert m["moves"] in moved
        reader = spec.load_module("layer_metrics", m["name"])
        assert reader.__file__.endswith(
            m["name"].rsplit(".", 1)[0] + ".py")
        # With no trace (`--trace 0`, or a reader's fault) it reads
        # nothing.
        assert reader.read(m, {"device_turn": None}) is None


@pytest.mark.parametrize("name,value", [
    (ONLINE[0], 19 / 3), (BATCH[0], 19 / 3), (ONLINE[1], 1.046),
    (ONLINE[2], 0.0), (ONLINE[3], 0.45), (BATCH[1], 0.45)])
def test_each_reader_reads_its_number_from_the_reduction(name, value):
    spec = Spec(ROOT, LONE_CELLS[0] if name in ONLINE else CLOSED_CELLS[0])
    reader = spec.load_module("layer_metrics", name)
    m = {"device_turn": turn.reduce_turn(_raw())}
    assert reader.read({"name": name}, m) == pytest.approx(value)
