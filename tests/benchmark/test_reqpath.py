"""A request's path and a tick's parts (`benchmarks/lib/reqpath.py`): the
join of `engine.launch` spans to their module events on hand-made
events, the whole reduction on two stretches recorded on the chip (docqa
and batch, cut by `benchmarks/checks/request_trace.py`, kept beside this
file as the plain lists `read_profile` returns), and the five entries
with their readers."""

import json
import os

import pytest

from checks import request_trace
from lib import progspans, reqpath
from lib.progspans import Span
from lib.spec import Spec

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MS = 1e6          # ns
# The engine's thread names itself (`tracing.name_thread`); its callers
# keep the process's name.
ENG, CALLER = "llm-engine", "python3"

ONLINE = ["engine.submit_to_launch_ms.online",
          "engine.first_token_overhead_ms.online",
          "engine.request_gap_idle_ms.online"]
BATCH = ["engine.host_cpu_ms_step.batch",
         "engine.admit_host_ms_tile.batch"]
LONE_CELLS = ["mistral7b-docqa-lone", "mellum2-repoctx-lone"]
BATCH_CELLS = ["internlm2-1b8-batch-closed"]


def _launch(program, seq, start_ms, dur_ms=0.5, cpu_us=300):
    return Span("engine.launch", start_ms * MS, dur_ms * MS, ENG,
                {"program": program, "seq": seq, "cpu_us": cpu_us})


def _module(program, start_ms, dur_ms):
    return (f"jit_{program}(123)", start_ms * MS, dur_ms * MS)


# -- the join ----------------------------------------------------------------

def test_whole_launches_join_in_order_by_program():
    launches = [_launch("decode_k8", 4, 1), _launch("prefill_sample_batch",
                                                    9, 2),
                _launch("decode_k8", 5, 40), _launch("decode_k8", 6, 80)]
    modules = [_module("decode_k8", 1.2, 38), _module("decode_k8", 40.1, 39),
               _module("prefill_sample_batch", 39.3, 0.7),
               _module("decode_k8", 80.2, 30),
               _module("sample_batch", 50, 0.1)]      # no span: not joined
    joined = reqpath.join(launches, modules)
    assert set(joined) == {"decode_k8", "prefill_sample_batch"}
    d = joined["decode_k8"]
    assert [(s.stats["seq"], m[1] / MS) for s, m in d.pairs] == [
        (4, 1.2), (5, 40.1), (6, 80.2)]
    assert d.summary() == {"launches": 3, "joined": 3, "skipped_modules": 0,
                           "cut_by_the_end": 0}
    (s, m), = joined["prefill_sample_batch"].pairs
    assert s.stats["seq"] == 9 and m[1] == 39.3 * MS


def test_launches_cut_by_the_edges_of_the_trace():
    """A module that began before the first span is an earlier launch's
    (the block in flight when the stretch began); the last span's module
    begins after the trace ends."""
    launches = [_launch("decode_k8", 5, 40), _launch("decode_k8", 6, 80),
                _launch("decode_k8", 7, 99)]
    modules = [_module("decode_k8", 1.2, 38.8),    # launch 4, before
               _module("decode_k8", 40.1, 39), _module("decode_k8", 80.2, 30)]
    d = reqpath.join(launches, modules)["decode_k8"]
    assert [(s.stats["seq"], m[1] / MS) for s, m in d.pairs] == [
        (5, 40.1), (6, 80.2)]
    assert d.summary() == {"launches": 3, "joined": 2, "skipped_modules": 0,
                           "cut_by_the_end": 1}


@pytest.mark.parametrize("seqs,told", [
    ((4, 6, 7), {"seq_holes": 1}),              # a span is missing
    ((4, 5, "x"), {"seq_holes": 3}),            # not a number at all
])
def test_a_hole_in_seq_gives_none_and_says_so(seqs, told):
    launches = [_launch("decode_k8", q, 10 * i) for i, q in enumerate(seqs)]
    modules = [_module("decode_k8", 10 * i + 1, 5) for i in range(3)]
    said = []
    assert reqpath.join(launches, modules,
                        lambda **kv: said.append(kv)) is None
    assert said == [dict(phase="request_path", join_failed="decode_k8",
                         launches=3, **told)]


def test_modules_of_launches_from_before_the_stretch_are_stepped_over():
    """Three tiles launched behind a running block, the stretch's edge
    between the first and the second: the first's module begins after
    the second's span, so pairing in order would put every module one
    launch late, and the third module before `its` span. The first
    pairing that keeps every module behind its span steps over it."""
    launches = [_launch("prefill_sample_batch", 381, 18.7),
                _launch("prefill_sample_batch", 382, 32.6)]
    modules = [_module("prefill_sample_batch", 19.1, 10.8),   # launch 380
               _module("prefill_sample_batch", 29.9, 11.1),
               _module("prefill_sample_batch", 41.0, 11.1)]
    j = reqpath.join(launches, modules)["prefill_sample_batch"]
    assert [(s.stats["seq"], m[1] / MS) for s, m in j.pairs] == [
        (381, 29.9), (382, 41.0)]
    assert j.summary() == {"launches": 2, "joined": 2, "skipped_modules": 1,
                           "cut_by_the_end": 0}


def test_a_module_may_begin_a_little_before_its_span():
    """The profiler's device timeline runs a millisecond or so ahead of
    its host one: a lone caller's tile, begun on the device within half
    a millisecond of its call, reads as beginning before it (docqa, my
    chip run, PR 36: pairing it with the next request's tile put the
    device's start 531.9 ms behind its launch)."""
    launches = [_launch("prefill_sample_batch", q, 2 + 530 * i, 1.6)
                for i, q in enumerate((50, 51, 52))]
    modules = [_module("prefill_sample_batch", 1.1 + 530 * i, 189)
               for i in range(3)]
    j = reqpath.join(launches, modules)["prefill_sample_batch"]
    assert [(s.stats["seq"], (m[1] - s.start) / MS) for s, m in j.pairs] \
        == [(q, pytest.approx(-0.9)) for q in (50, 51, 52)]
    # Further ahead than the clocks have been seen apart, it is another
    # launch's.
    modules = [_module("prefill_sample_batch", -0.5 + 530 * i, 189)
               for i in range(3)]
    j = reqpath.join(launches, modules)["prefill_sample_batch"]
    assert [(s.stats["seq"], m[1] / MS) for s, m in j.pairs] == [
        (50, 529.5), (51, 1059.5)] and j.cut == 1


def test_no_pairing_keeps_modules_behind_their_spans_gives_none(monkeypatch):
    monkeypatch.setattr(reqpath, "MAX_SHIFT", 1)
    launches = [_launch("decode_k8", q, 10 * q) for q in range(3)]
    modules = [_module("decode_k8", t, 0.5) for t in (1, 2, 3)]
    # (10 -> 2 and 20 -> 3 are early by more than the clocks' slack)
    said = []
    assert reqpath.join(launches, modules,
                        lambda **kv: said.append(kv)) is None
    assert said == [dict(phase="request_path", join_failed="decode_k8",
                         modules_before_their_span=1, launches=3,
                         modules=3)]


# -- the reduction, hand-made ------------------------------------------------

def _raw():
    """One lone request over a 100 ms stretch. The caller submits at 5;
    the engine's thread wakes, admits, builds and launches the tile at 6;
    the device starts it at 6.5 and ends at 26.5; the first token is out
    at 27.4; the block behind it is launched at 28 and starts at 30.5."""
    spans = [
        Span("engine.submit", 5 * MS, 0.05 * MS, CALLER,
             {"req": 7, "prompt_tokens": 3000}),
        Span("engine.idle_wait", 0, 5.2 * MS, ENG, {}),
        Span("engine.tick", 5.3 * MS, 22.3 * MS, ENG,
             {"tick": 3, "waiting": 1, "active": 0, "cpu_us": 2100}),
        Span("engine.admit", 5.4 * MS, 1.7 * MS, ENG,
             {"side": "slot", "taken": 1, "req_ids": "7", "cpu_us": 1500}),
        Span("engine.prefill_tile", 5.6 * MS, 1.4 * MS, ENG,
             {"side": "slot", "bucket": 4096, "rows": 1, "tile_rows": 1,
              "tokens": 3000, "req_ids": "7"}),
        Span("engine.tile_build", 5.7 * MS, 0.2 * MS, ENG, {}),
        _launch("prefill_sample_batch", 3, 6.0, 0.9, 700),
        Span("engine.deliver_first", 8 * MS, 19.5 * MS, ENG, {"tokens": 1}),
        Span("engine.fetch", 8 * MS, 19 * MS, ENG, {}),
        Span("engine.emit", 27.1 * MS, 0.3 * MS, ENG,
             {"first": 1, "req_ids": "7", "tokens": 1, "finished": 0,
              "cpu_us": 250}),
        Span("engine.tick", 27.7 * MS, 60 * MS, ENG,
             {"tick": 4, "waiting": 0, "active": 1, "cpu_us": 900}),
        Span("engine.dispatch_block", 27.9 * MS, 0.6 * MS, ENG,
             {"block": 9, "k": 4, "active": 1, "slots": 4}),
        _launch("decode_k4", 10, 28.0, 0.4, 300),
        Span("engine.process_block", 29 * MS, 58 * MS, ENG,
             {"block": 8, "k": 8, "slots": 4, "active": 1, "emitted": 8,
              "discarded": 0}),
        Span("engine.fetch", 29 * MS, 41.6 * MS, ENG, {}),
        Span("engine.emit", 70.7 * MS, 0.2 * MS, ENG,
             {"tokens": 8, "finished": 0, "cpu_us": 160}),
    ]
    modules = [_module("prefill_sample_batch", 6.5, 20),
               _module("decode_k4", 30.5, 40)]
    ops = [("%fusion.1 = bf16[8] fusion()", s, d) for _, s, d in modules]
    return {"spans": spans, "window": (0.0, 100 * MS), "scopes": {},
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}}


def test_a_requests_path_is_the_chain_of_its_id():
    rp = reqpath.reduce_paths(_raw())
    path, = rp.requests
    assert path["req"] == 7
    assert path["submit_to_launch"] == pytest.approx(1.0)
    assert path["tile_dev"] == pytest.approx(20.0)
    # Launch at 6.0 -> first token out at 27.4, less the tile's 20.
    assert path["first_token_overhead"] == pytest.approx(1.4)
    assert path["submit_to_first_token"] == pytest.approx(22.4) \
        == pytest.approx(sum(path[p] for p in reqpath.PARTS))
    assert rp.median("first_token_overhead") == pytest.approx(1.4)
    assert rp.tile_waits == pytest.approx([0.5])
    # No block before the tile: no turn from one request to the next.
    assert rp.request_gaps == [] and rp.request_gap_idle() is None


def test_the_overhead_around_a_tile_is_free_of_the_timelines_distance():
    """The device's timeline a millisecond ahead of the host's (every
    device event 1 ms earlier): the overhead and the path read the same,
    only the logged wait across the two moves."""
    raw = _raw()
    dev = raw["devices"]["/device:TPU:0"]
    for key in ("ops", "modules"):
        dev[key] = [(n, s - 1 * MS, d) for n, s, d in dev[key]]
    rp = reqpath.reduce_paths(raw)
    assert rp.requests == [pytest.approx(r) for r in
                           reqpath.reduce_paths(_raw()).requests]
    assert rp.tile_waits == pytest.approx([-0.5])


def test_the_devices_idle_time_between_two_requests():
    """The last block of the request before ends at 1.5, the tile begins
    at 6.5; a key's split runs in between and is not idle time."""
    raw = _raw()
    dev = raw["devices"]["/device:TPU:0"]
    dev["modules"][:0] = [("jit_decode_k4(5)", -30 * MS, 31.5 * MS),
                          ("jit__threefry_split(9)", 5.9 * MS, 0.01 * MS)]
    dev["ops"][:0] = [("%fusion.1 = bf16[8] fusion()", -30 * MS, 31.5 * MS),
                      ("%fusion.9 = u32[2] fusion()", 5.9 * MS, 0.01 * MS)]
    rp = reqpath.reduce_paths(raw)
    assert rp.request_gaps == pytest.approx([4.99])
    assert rp.request_gap_idle() == pytest.approx(4.99)
    got = rp.summary()["request_gap_idle_ms"]
    assert got["gaps"] == 1 and got["max"] == pytest.approx(4.99)
    assert got["mean_by_span"] == pytest.approx({
        "engine.idle_wait": 3.7, "no_program_span": 0.1, "engine.tick": 0.1,
        "engine.admit": 0.2, "engine.prefill_tile": 0.1 + 0.09,
        "engine.tile_build": 0.2, "engine.launch": 0.5})
    # A tile queued behind a running block waited for nothing idle.
    dev["modules"][0] = ("jit_decode_k4(5)", -30 * MS, 36.5 * MS)
    dev["ops"][0] = ("%fusion.1 = bf16[8] fusion()", -30 * MS, 36.5 * MS)
    assert reqpath.reduce_paths(raw).request_gaps == pytest.approx([0.0])


def test_the_hosts_cost_per_step_and_tile():
    rp = reqpath.reduce_paths(_raw())
    assert rp.host_cpu_ms_step() == pytest.approx((2.1 + 0.9) / 4)
    assert rp.admit_host_ms_tile() == pytest.approx(1.5)
    # Logged only: a block's blocked call, a token's emit.
    logged = rp.summary()
    assert logged["launch_blocked_ms_block"] == pytest.approx(0.4 - 0.3)
    assert logged["emit_host_us_token"] == pytest.approx((250 + 160) / 9)
    # A tick counts whole or not at all.
    raw = _raw()
    raw["window"] = (6 * MS, 100 * MS)
    rp = reqpath.reduce_paths(raw)
    assert rp.host["ticks"] == 1 and rp.admit_host_ms_tile() is None
    assert rp.host_cpu_ms_step() == pytest.approx(0.9 / 4)


def test_an_idle_gap_names_its_span_and_the_launch_awaited():
    rp = reqpath.reduce_paths(_raw())
    by_len = {round(g["idle_ms"], 3): g for g in rp.idle_gaps}
    gap = by_len[4.0]                   # 26.5 -> 30.5
    # By the innermost span at each instant: the first token's fetch
    # and emit, the next tick's dispatch and launch, then the fetch of
    # the block before; the longest part names the gap.
    assert gap["by_span_ms"] == pytest.approx({
        "engine.fetch": 0.5 + 1.5, "engine.deliver_first": 0.1 + 0.1,
        "engine.emit": 0.3, "no_program_span": 0.1,
        "engine.tick": 0.1 + 0.2 + 0.5,
        "engine.dispatch_block": 0.1 + 0.1, "engine.launch": 0.4})
    assert gap["span"] == "engine.fetch"
    assert gap["next_module"] == "jit_decode_k4"
    assert gap["awaited"] == pytest.approx({
        "program": "decode_k4", "seq": 10, "launch_began_ms_into_gap": 1.5,
        "module_began_ms_into_gap": 4.0, "launch_ms": 0.4})
    first = by_len[6.5]                 # 0 -> 6.5: the caller had not come
    assert first["span"] == "engine.idle_wait"
    assert first["awaited"]["program"] == "prefill_sample_batch"
    assert first["awaited"]["launch_began_ms_into_gap"] == pytest.approx(6.0)
    last = by_len[29.5]                 # 70.5 -> 100: nothing follows
    assert last["next_module"] is None and last["awaited"] is None


def test_the_launch_awaited_is_the_first_with_a_span_behind_the_gap():
    """A key's split runs between the gap and the tile it belongs to:
    the device was waiting for the tile."""
    raw = _raw()
    dev = raw["devices"]["/device:TPU:0"]
    dev["modules"].insert(0, ("jit__threefry_split(9)", 5.9 * MS, 0.003 * MS))
    dev["ops"].insert(0, ("%fusion.9 = u32[2] fusion()", 5.9 * MS,
                          0.003 * MS))
    rp = reqpath.reduce_paths(raw)
    gap, = [g for g in rp.idle_gaps if g["idle_ms"] == pytest.approx(5.9)]
    assert gap["next_module"] == "jit__threefry_split"
    assert gap["awaited"]["program"] == "prefill_sample_batch"
    assert gap["awaited"]["module_began_ms_into_gap"] == pytest.approx(6.5)


def test_a_program_without_the_spans_reads_as_nothing():
    raw = _raw()
    raw["spans"] = [s for s in raw["spans"] if s.name in (
        "engine.tick", "engine.prefill_tile", "engine.dispatch_block",
        "engine.deliver_first", "engine.process_block", "engine.fetch",
        "engine.idle_wait")]
    for s in raw["spans"]:
        s.stats.pop("cpu_us", None)
    rp = reqpath.reduce_paths(raw)
    assert rp.joined is None and rp.requests == []
    assert rp.median("submit_to_launch") is None
    for read in (rp.host_cpu_ms_step, rp.admit_host_ms_tile,
                 rp.request_gap_idle):
        assert read() is None
    assert reqpath.reduce_paths({"spans": [], "devices": {}}).summary()[
        "requests_whole"] == 0


# -- the reduction, recorded on the chip -------------------------------------

def _recorded(cell):
    path = os.path.join(HERE, f"recorded_request_trace.{cell}.json.gz")
    assert os.path.getsize(path) < 150_000
    return request_trace.load(path)


def test_a_docqa_stretch_recorded_on_the_chip_adds_up():
    raw, kept = _recorded("mistral7b-docqa-lone")
    said = []
    rp = reqpath.reduce_paths(raw, lambda **kv: said.append(kv))
    assert not said and rp.joined is not None
    want = kept["expect"]
    got = json.loads(json.dumps(rp.summary()))
    assert got["join"] == want["join"]
    assert got["path_median_ms"] == pytest.approx(want["path_median_ms"])
    assert got["host"] == pytest.approx(want["host"])
    assert [(g["span"], g["next_module"]) for g in got["idle_gaps"]] == [
        (g["span"], g["next_module"]) for g in want["idle_gaps"]]
    # What the chip's trace looked like, not only that the sums repeat:
    # every request submitted in the stretch has its whole chain, a tile
    # takes most of it, and the parts add up to the whole.
    assert rp.requests_submitted == len(rp.requests) >= 3
    for r in rp.requests:
        parts = [r[p] for p in reqpath.PARTS]
        assert all(p > 0 for p in parts)
        assert sum(parts) == pytest.approx(r["submit_to_first_token"])
        assert r["tile_dev"] > 0.9 * r["submit_to_first_token"]
        assert sum(parts) - r["tile_dev"] < 10.0
    # A lone caller: the device idles a few milliseconds from a request's
    # last block to the next one's tile, most of it inside an engine
    # span, and every one of the longest gaps has a span and a launch
    # awaited.
    assert len(rp.request_gaps) >= 3 and all(
        1 < g < 20 for g in rp.request_gaps)
    by_span = got["request_gap_idle_ms"]["mean_by_span"]
    assert sum(by_span.values()) == pytest.approx(
        got["request_gap_idle_ms"]["mean"])
    assert by_span.get("no_program_span", 0.0) < 0.2 * sum(by_span.values())
    for g in rp.idle_gaps:
        assert g["span"].startswith("engine.")
        assert sum(g["by_span_ms"].values()) == pytest.approx(g["idle_ms"])
        assert g["awaited"] is None or g["awaited"]["program"] in \
            rp.joined
    assert sum(g["awaited"] is not None for g in rp.idle_gaps) >= 8


def test_a_batch_stretch_recorded_on_the_chip_reads_the_hosts_costs():
    raw, kept = _recorded("internlm2-1b8-batch-closed")
    said = []
    rp = reqpath.reduce_paths(raw, lambda **kv: said.append(kv))
    assert not said and rp.joined is not None
    want = kept["expect"]
    got = json.loads(json.dumps(rp.summary()))
    assert got["join"] == want["join"]
    assert got["host"] == pytest.approx(want["host"])
    for key in ("host_cpu_ms_step", "admit_host_ms_tile",
                "launch_blocked_ms_block", "tile_wait_median_ms"):
        assert got[key] == pytest.approx(want[key]) and got[key] > 0, key
    h = rp.host
    # CPU time is a part of the time: of a tick, and of a launch.
    assert 0 < h["tick_cpu_us"] <= h["tick_us"]
    assert 0 <= h["decode_launch_blocked_us"] <= h["decode_launch_us"]
    assert any(p.startswith("decode_k") for p in rp.joined)
    assert sum(len(j.pairs) for j in rp.joined.values()) >= h["ticks"]
    # A tile waits behind the block in flight.
    assert rp.tile_waits and max(rp.tile_waits) > 1.0


@pytest.mark.parametrize("cell", ["mistral7b-docqa-lone",
                                  "internlm2-1b8-batch-closed"])
def test_the_callers_spans_leave_the_accepted_readers_as_they_were(cell):
    """`engine.submit` is a span of the caller's thread, and the accepted
    reduction (`progspans.reduce_profile`) nests spans by their thread's
    name: the engine's thread has a name of its own on the chip, and the
    reduction reads the same with the callers' spans and without."""
    raw, _ = _recorded(cell)
    submits = [s for s in raw["spans"] if s.name == "engine.submit"]
    others = [s for s in raw["spans"] if s.name != "engine.submit"]
    assert submits and {s.thread for s in others} == {ENG}
    assert ENG not in {s.thread for s in submits}
    seen = progspans.reduce_profile(raw)
    blind = progspans.reduce_profile(dict(raw, spans=others))
    self_s = seen.self_s_by_name()
    assert self_s.pop("engine.submit") > 0
    assert self_s == blind.self_s_by_name()
    assert seen.tick_host_ms() == blind.tick_host_ms() > 0
    assert seen.idle_gaps == blind.idle_gaps and seen.idle_gaps
    # A submit is an `engine.*` span too: idle time under one that no
    # span of the engine's thread covers counts as named, all of 0.1 ms.
    assert seen.idle_in_engine_span_s == pytest.approx(
        blind.idle_in_engine_span_s, abs=1e-4)


# -- once a run --------------------------------------------------------------

class _Ctx:
    def __init__(self, out_dir, trace):
        self.out_dir, self.trace, self.said = str(out_dir), trace, []

    def log(self, **kv):
        self.said.append(kv)


def test_for_run_reads_the_profile_once_and_keeps_it(tmp_path, monkeypatch):
    reads = []
    monkeypatch.setattr(reqpath.xplane, "find_xplane", lambda d: d + "/x.pb")
    monkeypatch.setattr(reqpath.progspans, "read_profile",
                        lambda path: reads.append(path) or _raw())
    m = {"ctx": _Ctx(tmp_path, 1)}
    rp = reqpath.for_run(m)
    assert reqpath.for_run(m) is rp and len(reads) == 1
    assert m["raw_profile"]["window"] == (0.0, 100 * MS)
    with open(tmp_path / "request_path.json") as f:
        assert json.load(f)["requests"][0]["req"] == 7
    line, = m["ctx"].said
    assert line["phase"] == "request_path" and line["requests_whole"] == 1
    # A profile someone has read already is not read again; no trace, no
    # reading.
    m2 = {"ctx": _Ctx(tmp_path, 1), "raw_profile": _raw()}
    assert reqpath.for_run(m2).median("tile_dev") == pytest.approx(20.0)
    off = {"ctx": _Ctx(tmp_path, 0)}
    assert reqpath.for_run(off) is None and len(reads) == 1
    assert off["ctx"].said == []


# -- the entries and their readers -------------------------------------------

def test_the_five_entries_are_the_last_of_per_layer(bench):
    last = bench["per_layer"][-5:]
    assert [m["name"] for m in last] == ONLINE + BATCH
    for m in last:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["layer"], m["better"]) == (
            "ms", "Engine", "lower")
        # The gap is read from the device's events alone.
        assert m["source"] == ("device_trace" if "gap" in m["name"]
                               else "program_span")
        online = m["name"] in ONLINE
        assert m["workloads"] == (LONE_CELLS if online else BATCH_CELLS)
        assert m["moves"] == ("ttft_p90_ms" if online else "serve_out_tok_s")


@pytest.mark.parametrize("cell", LONE_CELLS + BATCH_CELLS)
def test_the_readers_load_for_every_cell_they_list(cell):
    spec = Spec(ROOT, cell)
    mine = [m for m in spec.metrics("per_layer")
            if m["name"] in ONLINE + BATCH]
    assert [m["name"] for m in mine] == (
        ONLINE if cell in LONE_CELLS else BATCH)
    moved = {m["name"] for m in spec.metrics("end_to_end")}
    for m in mine:
        assert m["moves"] in moved
        reader = spec.load_module("layer_metrics", m["name"])
        assert reader.__file__.endswith(
            m["name"].rsplit(".", 1)[0] + ".py")
        # With no trace (a parent's run, `--trace 0`) it reads nothing.
        assert reader.read(m, {"request_path": None}) is None


@pytest.mark.parametrize("name,value", [
    (ONLINE[0], 1.0), (ONLINE[1], 1.4), (ONLINE[2], 5.0),
    (BATCH[0], 0.75), (BATCH[1], 1.5)])
def test_each_reader_reads_its_number_from_the_reduction(name, value):
    spec = Spec(ROOT, LONE_CELLS[0] if name in ONLINE else BATCH_CELLS[0])
    reader = spec.load_module("layer_metrics", name)
    raw = _raw()
    dev = raw["devices"]["/device:TPU:0"]
    dev["modules"].insert(0, ("jit_decode_k4(5)", -30 * MS, 31.5 * MS))
    dev["ops"].insert(0, ("%fusion.1 = bf16[8] fusion()", -30 * MS,
                          31.5 * MS))
    m = {"request_path": reqpath.reduce_paths(raw)}
    assert reader.read({"name": name}, m) == pytest.approx(value)
