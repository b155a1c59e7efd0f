"""The program naming itself: `tracing.span()`'s two sinks and its free
off state, the engine's spans and counts, `steps_waited`, the serving
programs' names, the kernels' names and the train step's phase scopes.
All on the CPU; nothing sleeps or asserts a duration."""

import contextlib
import dataclasses
import importlib
import re
import threading
import uuid

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import configs, generate
from ray_tpu.models.transformer import init_params
from ray_tpu.serve import llm
from ray_tpu.serve.llm import DEVICE_OPS, LLMEngine
from ray_tpu.util import tracing


@pytest.fixture(scope="module")
def tiny_model():
    cfg = configs.tiny_test()
    return cfg, init_params(cfg, jax.random.key(0))


@pytest.fixture
def hook():
    """The spans that finish on the test's own thread. The hook is the
    process's: in a worker shared with other test files, an engine
    thread one of them left running reports its ticks here too."""
    got, own = [], threading.get_ident()

    def mine(event):
        if threading.get_ident() == own:
            got.append(event)

    tracing.setup_tracing(mine)
    yield got
    tracing.clear_tracing()


class _Annotation:
    """Stands in for jax.profiler.TraceAnnotation: records what entered."""

    entered = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, dict(kw)

    def __enter__(self):
        _Annotation.entered.append(self)
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **kw):
        self.kw.update(kw)


@pytest.fixture
def annotations(monkeypatch):
    _Annotation.entered = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    return _Annotation.entered


def _boom(*a, **k):
    raise AssertionError("not in the off state")


def test_span_with_no_sink_is_the_profiler_annotation_alone(
        monkeypatch, annotations):
    tracing.clear_tracing()
    assert not tracing._recording()
    monkeypatch.setattr(uuid, "uuid4", _boom)
    monkeypatch.setattr(tracing, "trace_sampled", _boom)
    sp = tracing.span("engine.tick", tick=3, waiting=0)
    with sp as span_id:
        sp.set(emitted=5)
    assert span_id is None
    assert [(a.name, a.kw) for a in annotations] == [
        ("ray_tpu:engine.tick", {"tick": 3, "waiting": 0, "emitted": 5})]
    assert tracing.current_span_id() is None


def test_span_under_a_hook_keeps_ids_parents_and_late_attributes(
        hook, annotations):
    outer = tracing.span("outer", a=1)
    with outer as oid:
        with tracing.span("inner") as iid:
            assert tracing.current_span_id() == iid
        outer.set(b=2)
    inner_ev, outer_ev = hook
    assert outer_ev["args"] == {"parent": None, "a": 1, "b": 2,
                                "trace_id": outer_ev["args"]["trace_id"]}
    assert inner_ev["args"]["parent"] == oid
    assert inner_ev["args"]["trace_id"] == outer_ev["args"]["trace_id"]
    assert outer_ev["dur"] >= inner_ev["dur"] >= 0
    assert outer_ev["tid"] == f"span:{oid}"
    # Both sinks: the profiler saw the same two spans.
    assert [a.name for a in annotations] == ["ray_tpu:outer",
                                             "ray_tpu:inner"]
    assert annotations[0].kw == {"a": 1, "b": 2}


def test_a_span_does_not_swallow_an_exception(hook):
    with pytest.raises(KeyError):
        with tracing.span("boom"):
            raise KeyError("x")
    assert [e["name"] for e in hook] == ["boom"]
    assert tracing.current_span_id() is None


def test_a_cpu_span_carries_the_threads_cpu_time_to_both_sinks(
        hook, annotations):
    with tracing.span("outer", cpu=True, a=1):
        with tracing.span("inner"):
            sum(range(20_000))
    inner_ev, outer_ev = hook
    assert "cpu_us" not in inner_ev["args"]           # off by default
    assert 0 < outer_ev["args"]["cpu_us"] <= outer_ev["dur"]
    assert annotations[0].kw == {"a": 1,
                                 "cpu_us": outer_ev["args"]["cpu_us"]}
    assert "cpu" not in annotations[0].kw and annotations[1].kw == {}


def test_a_cpu_span_with_no_hook_and_no_session_costs_two_clock_reads(
        monkeypatch):
    tracing.clear_tracing()
    monkeypatch.setattr(uuid, "uuid4", _boom)
    monkeypatch.setattr(tracing, "trace_sampled", _boom)
    sp = tracing.span("engine.tick", cpu=True, tick=3)
    with sp as span_id:
        pass
    assert span_id is None and sp.attributes["cpu_us"] >= 0
    with pytest.raises(KeyError):
        with tracing.span("boom", cpu=True):
            raise KeyError("x")


def _os_name(native_id):
    with open(f"/proc/self/task/{native_id}/comm") as f:
        return f.read().strip()


def test_a_thread_names_itself_to_the_os_and_no_other_thread():
    """What a profiler session labels a thread's spans by."""
    before = _os_name(threading.get_native_id())
    seen = []
    t = threading.Thread(target=lambda: (
        tracing.name_thread("a-name-longer-than-15-bytes"),
        seen.append(_os_name(threading.get_native_id()))))
    t.start()
    t.join()
    assert seen == ["a-name-longer-t"]
    assert _os_name(threading.get_native_id()) == before


def test_the_engines_thread_has_a_name_of_its_own(tiny_model):
    """`engine.submit` is a span of the caller's thread: a profile keeps
    the engine's spans apart from it by the thread's name."""
    engine = _engine(tiny_model)
    thread = engine.start()
    try:
        req = engine.submit([1, 2, 3], max_new_tokens=2)
        assert len(req.result(timeout=120)) == 2
        assert _os_name(thread.native_id) == "llm-engine"
        assert _os_name(threading.get_native_id()) != "llm-engine"
    finally:
        engine.stop()
        thread.join(timeout=60)


def _drain(engine, reqs):
    for _ in range(10_000):
        if all(r.finish_ts for r in reqs):
            break
        engine.step()
    engine.step()           # the tick that processes the last block
    assert all(r.finish_ts for r in reqs)


def _engine(tiny_model, **kw):
    cfg, params = tiny_model
    return LLMEngine(cfg, params, num_slots=2, max_seq_len=64,
                     decode_block=8, **kw)


@pytest.fixture
def drained(tiny_model, hook):
    """An engine run to the end under a hook: five requests on two
    slots, so some wait and get their first token queue-side."""
    engine = _engine(tiny_model)
    reqs = [engine.submit(list(range(1, 4 + 3 * i)), max_new_tokens=3 + i)
            for i in range(5)]
    _drain(engine, reqs)
    return engine, reqs, list(hook)


def test_engine_spans_nest_under_their_tick_and_share_its_trace(drained):
    _, _, events = drained
    by_id = {e["tid"].split(":", 1)[1]: e for e in events}
    names = {e["name"] for e in events}
    assert {"engine.tick", "engine.admit", "engine.prefill_tile",
            "engine.tile_build", "engine.launch", "engine.fuse_first",
            "engine.dispatch_block", "engine.deliver_first",
            "engine.process_block", "engine.fetch", "engine.emit",
            "engine.submit", "engine.device_call"} == names
    parents = {
        "engine.admit": {"engine.tick"},
        "engine.dispatch_block": {"engine.tick"},
        "engine.process_block": {"engine.tick"},
        "engine.deliver_first": {"engine.tick"},
        "engine.fuse_first": {"engine.tick"},
        "engine.prefill_tile": {"engine.admit"},
        "engine.tile_build": {"engine.prefill_tile"},
        "engine.launch": {"engine.prefill_tile", "engine.dispatch_block"},
        "engine.fetch": {"engine.process_block", "engine.deliver_first"},
        "engine.emit": {"engine.process_block", "engine.deliver_first"},
        "engine.device_call": {"engine.launch", "engine.admit",
                               "engine.prefill_tile", "engine.fuse_first",
                               "engine.fetch"}}
    for e in events:
        if e["name"] in parents:
            parent = by_id[e["args"]["parent"]]
            assert parent["name"] in parents[e["name"]], e["name"]
            assert parent["args"]["trace_id"] == e["args"]["trace_id"]
        elif e["name"] == "engine.submit":      # the caller's, a root
            assert e["args"]["parent"] is None
    ticks = [e["args"]["tick"] for e in events if e["name"] == "engine.tick"]
    assert sorted(ticks) == list(range(len(ticks)))
    # No span per token or per slot: one emit a block, one a tick's
    # first tokens.
    count = {n: sum(e["name"] == n for e in events) for n in names}
    assert count["engine.emit"] == (count["engine.process_block"]
                                    + count["engine.deliver_first"])
    assert count["engine.launch"] == (count["engine.prefill_tile"]
                                      + count["engine.dispatch_block"])
    assert count["engine.tile_build"] == count["engine.prefill_tile"]


def test_the_new_spans_carry_their_attributes(drained):
    engine, reqs, events = drained

    def spans(name):
        return [e for e in events if e["name"] == name]

    keys = {"parent", "trace_id"}
    for e in spans("engine.submit"):
        assert set(e["args"]) == keys | {"req", "prompt_tokens"}
    assert [(e["args"]["req"], e["args"]["prompt_tokens"])
            for e in spans("engine.submit")] == [
        (r.id, len(r.prompt)) for r in reqs]
    for e in spans("engine.admit"):
        a = e["args"]
        assert set(a) == keys | {"side", "req_ids", "cpu_us"}
        assert a["side"] in ("slot", "queue") and a["req_ids"].split()
    assert {e["args"]["side"] for e in spans("engine.admit")} == {
        "slot", "queue"}
    assert all(set(e["args"]) == keys for e in spans("engine.tile_build"))
    # How many parts it joins is the concatenation's `n`.
    assert all(set(e["args"]) == keys for e in spans("engine.fuse_first"))
    by_parent = {}
    for c in spans("engine.device_call"):
        by_parent.setdefault(c["args"]["parent"], []).append(c["args"])
    for e in spans("engine.fuse_first"):
        inside = by_parent[e["tid"].split(":", 1)[1]]
        assert [c["op"] for c in inside if c["op"] != "stack"] == [
            "concatenate", "copy_start"]
    programs = set()
    for e in spans("engine.launch"):
        assert set(e["args"]) == keys | {"program", "seq", "cpu_us"}
        programs.add(e["args"]["program"])
        # Its children are the calls it makes, its own program's among
        # them under its `program` and `seq`.
        inside = [c["args"] for c in spans("engine.device_call")
                  if c["args"]["parent"] == e["tid"].split(":", 1)[1]]
        own, = [c for c in inside if c.get("seq") is not None]
        assert (own["op"], own["program"], own["seq"]) == (
            "program", e["args"]["program"], e["args"]["seq"])
        block = e["args"]["program"].startswith("decode_k")
        assert set(own) == keys | {"op", "call", "program", "seq"} | (
            {"k"} if block else set())
        assert [c["op"] for c in inside][:2] == (
            ["split", "to_device"] if block else ["to_device", "program"])
    # A fetch names the call whose result it reads, and that call's
    # program and `seq` where it is a launch's.
    by_call = {c["args"]["call"]: c["args"]
               for c in spans("engine.device_call")}
    for e in spans("engine.fetch"):
        a = e["args"]
        waited = by_call[a["call"]]
        assert set(a) == keys | {"call"} | (
            {"program"} if "program" in waited else set()) | (
            {"seq"} if "seq" in waited else set())
        assert waited["op"] == ("program" if "program" in a
                                else "concatenate")
        assert all(a[k] == waited[k] for k in ("program", "seq") if k in a)
    assert {"prefill_sample_batch", "first_token_sample"} <= programs
    assert programs - {"prefill_sample_batch", "first_token_sample"} <= {
        f"decode_k{k}" for k in (1, 2, 4, 8)}
    for e in spans("engine.emit"):
        a = e["args"]
        first = {"first", "req_ids"} if "first" in a else set()
        assert set(a) == keys | {"tokens", "finished", "cpu_us"} | first
        if first:
            assert a["first"] == 1 and a["tokens"] == len(a["req_ids"].split())
    assert sum(e["args"]["finished"] for e in spans("engine.emit")) \
        == len(reqs)
    assert all("cpu_us" in e["args"] for e in spans("engine.tick"))
    # CPU time is a part of the span's length, and a tick that handed
    # tokens over spent some.
    by_id = {e["tid"].split(":", 1)[1]: e for e in events}
    emitting = {by_id[e["args"]["parent"]]["args"]["parent"]
                for e in spans("engine.emit") if e["args"]["tokens"]}
    for e in events:
        if "cpu_us" in e["args"]:
            assert 0 <= e["args"]["cpu_us"] <= e["dur"], e["name"]
    for tick in spans("engine.tick"):
        if tick["tid"].split(":", 1)[1] in emitting:
            assert tick["args"]["cpu_us"] > 0


def test_one_request_shares_its_id_from_submit_to_its_first_token(drained):
    _, reqs, events = drained
    by_id = {e["tid"].split(":", 1)[1]: e for e in events}

    def holding(name, rid, **where):
        return [e for e in events if e["name"] == name
                and str(rid) in e["args"].get("req_ids", "").split()
                and all(e["args"].get(k) == v for k, v in where.items())]

    for r in reqs:
        submit, = [e for e in events if e["name"] == "engine.submit"
                   and e["args"]["req"] == r.id]
        # The tile whose program gives the first token: the earliest.
        tile = min(holding("engine.prefill_tile", r.id),
                   key=lambda e: e["ts"])
        launch, = [e for e in events if e["name"] == "engine.launch"
                   and by_id[e["args"]["parent"]] is tile]
        emit, = holding("engine.emit", r.id, first=1)
        assert holding("engine.admit", r.id)
        assert submit["ts"] <= tile["ts"] <= launch["ts"] <= emit["ts"]
        assert launch["args"]["program"] == (
            "prefill_sample_batch" if tile["args"]["side"] == "slot"
            else "first_token_sample")


def test_counts_equal_the_sums_of_the_span_attributes(drained):
    engine, reqs, events = drained

    def spans(name):
        return [e["args"] for e in events if e["name"] == name]

    c = engine.stats()["counts"]
    tiles, blocks = spans("engine.prefill_tile"), spans("engine.dispatch_block")
    done = spans("engine.process_block")
    assert c["ticks"] == len(spans("engine.tick"))
    assert c["blocks"] == len(blocks) == len(done)
    assert c["blocks_by_k"] == {k: sum(b["k"] == k for b in blocks)
                                for k in {b["k"] for b in blocks}}
    assert c["slot_steps"] == sum(b["k"] * b["slots"] for b in blocks)
    assert c["tokens_discarded"] == sum(b["discarded"] for b in done)
    assert all(b["discarded"] == b["k"] * b["active"] - b["emitted"]
               for b in done)
    assert c["prefill_tiles"] == len(tiles)
    assert c["prefill_rows"] == sum(t["rows"] for t in tiles)
    assert c["prefill_tile_rows"] == sum(t["tile_rows"] for t in tiles)
    assert c["prefill_tokens"] == sum(t["tokens"] for t in tiles)
    assert c["prefill_tile_tokens"] == sum(t["tile_rows"] * t["bucket"]
                                           for t in tiles)
    assert c["queue_side_first_tokens"] == sum(
        t["rows"] for t in tiles if t["side"] == "queue") > 0
    # The engine thread's clock.
    launches = spans("engine.launch")
    assert c["submitted"] == len(spans("engine.submit")) == len(reqs)
    assert c["launches"] == {p: sum(x["program"] == p for x in launches)
                             for p in {x["program"] for x in launches}}
    for p, n in c["launches"].items():      # numbered without a hole
        assert [x["seq"] for x in launches if x["program"] == p] \
            == list(range(n))
    assert c["tick_cpu_ns"] == 1000 * sum(
        t["cpu_us"] for t in spans("engine.tick")) > 0
    # Read outside the spans they sum: never less.
    durs = {n: 1000 * sum(e["dur"] for e in events if e["name"] == n)
            for n in ("engine.tick", "engine.fetch")}
    assert c["tick_ns"] >= durs["engine.tick"] > 0
    assert c["tick_ns"] >= c["tick_cpu_ns"]
    assert c["fetch_wait_ns"] >= durs["engine.fetch"] > 0
    assert c["idle_wait_ns"] == 0           # no `run_forever` here
    assert engine.stats()["counts"]["launches"] is not c["launches"]
    assert sum(k * n for k, n in c["blocks_by_k"].items()) \
        == engine.decode_ticks == engine.steps_processed
    # Every token is a first token or a block's.
    assert engine.tokens_out == sum(len(r.tokens) for r in reqs) == sum(
        b["emitted"] for b in done) + sum(
            d["tokens"] for d in spans("engine.deliver_first"))
    # A tile names the requests it holds; every request is in a slot tile.
    in_slot_tiles = sorted(int(i) for t in tiles if t["side"] == "slot"
                           for i in t["req_ids"].split())
    assert in_slot_tiles == sorted(r.id for r in reqs)
    assert all("," not in t["req_ids"] for t in tiles)


# -- every device call of the engine's thread is a span -----------------------

# Where each `op` of the vocabulary is made: the spans it may stand
# directly under. Every one of them lies in an `engine.launch`, an
# `engine.admit`, an `engine.fuse_first` or an `engine.fetch`.
OP_PARENTS = {
    "split": {"engine.launch", "engine.admit", "engine.prefill_tile"},
    "to_device": {"engine.launch", "engine.admit"},
    "program": {"engine.launch"},
    "slice": {"engine.launch", "engine.admit"},
    "scatter": {"engine.admit", "engine.launch"},
    "pad": {"engine.prefill_tile"},
    "stack": {"engine.fuse_first"},
    "concatenate": {"engine.fuse_first"},
    "copy_start": {"engine.launch", "engine.admit", "engine.fuse_first"},
    "to_host": {"engine.fetch"},
}
CALL_ROOTS = {"engine.launch", "engine.admit", "engine.fuse_first",
              "engine.fetch"}


@pytest.fixture
def padded(tiny_model, hook):
    """A queue-side tile past the 1024 bucket is narrower than
    `_ADMIT_TILE` and its results are padded: two prompts of 1,100
    tokens on one slot."""
    cfg, params = tiny_model
    before = len(hook)          # another fixture's engine may have run
    engine = LLMEngine(cfg, params, num_slots=1, max_seq_len=2560,
                       decode_block=2)
    reqs = [engine.submit([5] * 1100, max_new_tokens=2) for _ in range(2)]
    _drain(engine, reqs)
    return engine, reqs, hook[before:]


def test_every_op_of_the_vocabulary_appears_under_its_parent(
        drained, padded):
    assert set(OP_PARENTS) == set(DEVICE_OPS)
    seen = {}
    for engine, _, events in (drained, padded):
        by_id = {e["tid"].split(":", 1)[1]: e for e in events}
        calls = [e for e in events if e["name"] == "engine.device_call"]
        for e in calls:
            a = e["args"]
            parent = by_id[a["parent"]]
            assert parent["name"] in OP_PARENTS[a["op"]], a
            seen.setdefault(a["op"], set()).add(parent["name"])
            up = parent
            while up["name"] not in CALL_ROOTS:
                up = by_id[up["args"]["parent"]]
            assert set(a) >= {"op", "call"}
            assert ("n" in a) == (a["op"] in (
                "to_device", "copy_start", "to_host", "stack",
                "concatenate")), a
        # `call` runs without a hole, in the order the calls were made.
        assert [e["args"]["call"] for e in calls] == list(range(len(calls)))
        # The counters are the spans' sums, by `op`.
        c = engine.stats()["counts"]
        assert c["device_calls"] == {
            op: sum(e["args"]["op"] == op for e in calls)
            for op in {e["args"]["op"] for e in calls}}
        assert sum(c["device_calls"].values()) == len(calls)
        for op, ns in c["device_call_ns"].items():     # read just outside
            assert ns >= 1000 * sum(e["dur"] for e in calls
                                    if e["args"]["op"] == op) > 0
        assert engine.stats()["counts"]["device_calls"] \
            is not c["device_calls"]
    assert set(seen) == set(DEVICE_OPS)
    assert seen["pad"] == {"engine.prefill_tile"}
    assert seen["split"] == OP_PARENTS["split"]


def test_a_fetch_of_first_tokens_leads_back_to_its_tile(drained):
    """The first tokens' fetch waits for the fusion's concatenation: the
    calls before it, back to the tile's program, are the eager programs
    the tokens went through."""
    _, _, events = drained
    calls = {e["args"]["call"]: e["args"] for e in events
             if e["name"] == "engine.device_call"}
    by_id = {e["tid"].split(":", 1)[1]: e for e in events}
    firsts = [e for e in events if e["name"] == "engine.fetch"
              and by_id[e["args"]["parent"]]["name"]
              == "engine.deliver_first"]
    assert firsts
    for e in firsts:
        back = e["args"]["call"]
        assert calls[back]["op"] == "concatenate"
        walked = []
        while calls[back]["op"] != "program":
            back -= 1
            walked.append(calls[back]["op"])
        assert calls[back]["program"] in ("prefill_sample_batch",
                                          "first_token_sample")
        assert set(walked) <= {"stack", "slice", "scatter", "to_device",
                               "copy_start", "pad", "program"}


class _Guard:
    """The device-facing names `serve/llm.py` reaches the device through,
    wrapped: each use inside an engine tick is counted, with whether an
    `engine.device_call` was open around it."""

    def __init__(self):
        self.ticks = self.open = 0
        self.uses, self.outside, self.per_call = [], [], []

    def wrap(self, what, fn, only=lambda *a, **k: True):
        def guarded(*a, **k):
            if self.ticks and only(*a, **k):
                self.uses.append(what)
                if self.open:
                    self.per_call[-1] += 1
                else:
                    self.outside.append(what)
            return fn(*a, **k)
        return guarded

    def proxy(self, module, names):
        guard = self

        class Proxy:
            def __getattr__(self, name):
                attr = getattr(module, name)
                if name in names:
                    return guard.wrap(f"{module.__name__}.{name}", attr,
                                      names[name])
                return attr
        return Proxy()


@pytest.fixture
def guard(monkeypatch):
    """Every way `serve/llm.py` asks something of the device, guarded:
    the `jnp` and `jax.random` functions it uses, the programs, the host
    copies' start, `np.asarray` of a device array, and a device array's
    own index and `.at[].set`."""
    from jax._src import array as jax_array
    from jax._src.numpy import array_methods

    g = _Guard()
    always = lambda *a, **k: True                       # noqa: E731
    on_device = lambda x, *a, **k: isinstance(x, jax.Array)  # noqa: E731
    monkeypatch.setattr(llm, "jnp", g.proxy(jnp, dict.fromkeys(
        ("asarray", "pad", "stack", "concatenate", "zeros", "array"),
        always)))
    random = g.proxy(jax.random, {"split": always})
    monkeypatch.setattr(llm, "jax", g.proxy(jax, {}))
    monkeypatch.setattr(llm.jax, "random", random, raising=False)
    monkeypatch.setattr(llm, "np", g.proxy(llm.np, {"asarray": on_device}))
    for name in ("prefill_sample_batch", "prefill_suffix_batch",
                 "prefill_block_batch", "first_token_sample",
                 "first_token_suffix_sample", "decode_step", "decode_multi",
                 "decode_block_multi", "_sample_batch",
                 "_copy_to_host_async", "compute_prefix_kv"):
        monkeypatch.setattr(llm, name, g.wrap(name, getattr(llm, name)))
    monkeypatch.setattr(jax_array.ArrayImpl, "__getitem__", g.wrap(
        "Array[...]", jax_array.ArrayImpl.__getitem__))
    monkeypatch.setattr(array_methods._IndexUpdateRef, "set", g.wrap(
        "Array.at[].set", array_methods._IndexUpdateRef.set))

    step, device_call = LLMEngine.step, LLMEngine._device_call

    def counted_step(self):
        g.ticks += 1
        try:
            return step(self)
        finally:
            g.ticks -= 1

    @contextlib.contextmanager
    def counted_call(self, op, **attributes):
        with device_call(self, op, **attributes) as call:
            g.open += 1
            g.per_call.append(0)
            try:
                yield call
            finally:
                g.open -= 1

    monkeypatch.setattr(LLMEngine, "step", counted_step)
    monkeypatch.setattr(LLMEngine, "_device_call", counted_call)
    return g


def _run_guarded(cfg, slots=2):
    params = init_params(cfg, jax.random.key(0))
    engine = LLMEngine(cfg, params, num_slots=slots, max_seq_len=64,
                       decode_block=8)
    reqs = [engine.submit(list(range(1, 4 + 3 * i)), max_new_tokens=3 + i)
            for i in range(5)]
    _drain(engine, reqs)
    return engine


@pytest.mark.parametrize("preset", ["tiny_test", "tiny_afmoe_test",
                                    "tiny_sdar_test"])
def test_no_device_call_of_a_tick_lies_outside_a_span(guard, preset):
    """The list of call sites is closed: a tick's every use of a
    device-facing name happens inside an `engine.device_call`, each span
    holds at least one, and the counters count the spans. A call added
    to the engine's thread without its span fails here."""
    engine = _run_guarded(getattr(configs, preset)())
    assert guard.outside == []
    assert len(guard.uses) >= len(guard.per_call) > 50
    assert all(n >= 1 for n in guard.per_call)
    calls = engine.stats()["counts"]["device_calls"]
    assert sum(calls.values()) == len(guard.per_call) == engine._calls
    assert set(calls) <= set(DEVICE_OPS)


def test_the_guard_sees_a_call_made_outside_a_span(guard, monkeypatch):
    """The control: the host copies started without their span."""
    monkeypatch.setattr(
        LLMEngine, "_start_host_copy",
        lambda self, *arrays: llm._copy_to_host_async(*arrays))
    _run_guarded(configs.tiny_test())
    assert guard.outside and set(guard.outside) == {"_copy_to_host_async"}


# What an admission tick builds: (prompt lengths submitted together,
# slots, the slot tiles as (bucket, rows, tile_rows)). Buckets of a
# 640-row engine: 16 ... 512, 640; a tile holds `_TILE_POSITIONS` = 512
# positions, at most `_ADMIT_TILE` = 8 rows and at least one. Under the
# two bf16 terms of float32 activations on bf16 weights (the last cases:
# the tiny model's weights cast to bf16) it holds 256.
TILES = {
    "a_lone_long_prompt_is_one_row": ([300], 2, [(512, 1, 1)]),
    "eight_of_the_smallest_bucket_share_a_tile": (
        [3, 5, 16, 9, 2, 11, 7, 4], 8, [(16, 8, 8)]),
    "three_of_a_one_row_bucket_are_three_tiles": (
        [300, 400, 512], 3, [(512, 1, 1)] * 3),
    "five_of_the_128_bucket_are_two_tiles_of_four": (
        [100] * 5, 5, [(128, 4, 4), (128, 1, 4)]),
    "three_of_the_256_bucket_are_two_tiles_of_two": (
        [200, 129, 256], 4, [(256, 2, 2), (256, 1, 2)]),
    "past_the_last_power_of_two_is_one_row": ([600], 1, [(640, 1, 1)]),
    "a_mixed_wave_is_cut_bucket_by_bucket": (
        [10, 300, 12, 120, 301], 8,
        [(16, 2, 8), (128, 1, 4), (512, 1, 1), (512, 1, 1)]),
    "three_short_buckets_of_one_term": (
        [60, 100, 250], 4, [(64, 1, 8), (128, 1, 4), (256, 1, 2)]),
    "two_terms_halve_the_three_short_buckets": (
        [60, 100, 250], 4, [(64, 1, 4), (128, 1, 2), (256, 1, 1)]),
    "two_terms_make_five_of_the_128_bucket_three_tiles": (
        [100] * 5, 5, [(128, 2, 2), (128, 2, 2), (128, 1, 2)]),
}


def _two_terms(model):
    """The tiny model in float32 on bf16 weights: `moe.dot_terms` 2."""
    cfg, params = model
    return (dataclasses.replace(cfg, param_dtype=jnp.bfloat16),
            jax.tree.map(lambda a: a.astype(jnp.bfloat16), params))


@pytest.mark.parametrize("case", sorted(TILES))
def test_a_slot_tile_is_as_wide_as_its_bucket_needs(tiny_model, hook, case):
    """The wave is admitted by one tick, every request streams all its
    tokens, and the counts are the sums of the spans' attributes."""
    terms = 2 if case.startswith("two_terms") else 1
    cfg, params = _two_terms(tiny_model) if terms == 2 else tiny_model
    lens, slots, want = TILES[case]
    engine = LLMEngine(cfg, params, num_slots=slots, max_seq_len=640,
                       decode_block=4)
    reqs = [engine.submit([1 + (i + j) % 250 for j in range(n)],
                          max_new_tokens=3) for i, n in enumerate(lens)]
    engine.step()
    tiles = [e["args"] for e in hook if e["name"] == "engine.prefill_tile"]
    assert [(t["bucket"], t["rows"], t["tile_rows"]) for t in tiles] == want
    assert all(t["side"] == "slot" for t in tiles)
    assert len({t["parent"] for t in tiles}) == 1        # one tick
    assert all(t["tile_rows"] == LLMEngine._tile_rows(t["bucket"], terms)
               for t in tiles)
    _drain(engine, reqs)
    assert [len(list(r)) for r in reqs] == [3] * len(lens)
    c = engine.stats()["counts"]
    assert c["prefill_tiles"] == len(want)
    assert c["prefill_rows"] == len(lens)
    assert c["prefill_tile_rows"] == sum(t["tile_rows"] for t in tiles)
    assert c["prefill_tokens"] == sum(lens) == sum(
        t["tokens"] for t in tiles)
    assert c["prefill_tile_tokens"] == sum(t["tile_rows"] * t["bucket"]
                                           for t in tiles)


@pytest.mark.parametrize("terms,n,bucket,slot_rows", [
    (1, 300, 512, 1), (1, 100, 128, 4), (2, 100, 128, 2)])
def test_a_queue_side_tile_keeps_the_widest_width(tiny_model, hook, terms, n,
                                                  bucket, slot_rows):
    """A prompt that finds no slot gets its first token from an
    `_ADMIT_TILE`-row tile, whatever the model's terms, then a slot tile
    of its bucket's rows when a slot frees."""
    cfg, params = _two_terms(tiny_model) if terms == 2 else tiny_model
    engine = LLMEngine(cfg, params, num_slots=1, max_seq_len=640,
                       decode_block=4)
    reqs = [engine.submit([7] * n, max_new_tokens=3) for _ in range(2)]
    _drain(engine, reqs)
    tiles = [(t["args"]["side"], t["args"]["bucket"], t["args"]["tile_rows"])
             for t in hook if t["name"] == "engine.prefill_tile"]
    assert tiles == [("slot", bucket, slot_rows),
                     ("queue", bucket, LLMEngine._ADMIT_TILE),
                     ("slot", bucket, slot_rows)]
    assert reqs[0].tokens == reqs[1].tokens and len(reqs[1].tokens) == 3


def test_steps_waited_counts_the_blocks_between_a_request_and_the_device(
        tiny_model):
    engine = _engine(tiny_model)
    first = engine.submit([1, 2, 3], max_new_tokens=9)
    engine.step()
    # Admitted by the first tick after its submit, nothing in flight.
    assert (first.admit_tick, first.steps_waited) == (0, 0)
    k = engine.decode_ticks
    assert k == 8 and engine.steps_processed == 0
    # Submitted just after a dispatch: that block stands before it.
    second = engine.submit([4, 5, 6], max_new_tokens=2)
    engine.step()
    assert (second.admit_tick, second.steps_waited) == (1, k)
    _drain(engine, [first, second])
    # Once the host has read every block, a new request waits for none.
    third = engine.submit([7, 8], max_new_tokens=2)
    engine.step()
    assert third.steps_waited == 0 and third.admit_tick > 1
    _drain(engine, [third])


def test_finished_is_a_ring_and_stats_counts_all(tiny_model, monkeypatch):
    monkeypatch.setattr(LLMEngine, "FINISHED_RING", 3)
    engine = _engine(tiny_model)
    reqs = [engine.submit([1, 2 + i], max_new_tokens=2) for i in range(5)]
    _drain(engine, reqs)
    st = engine.stats()
    assert st["finished"] == 5 and len(engine.finished) == 3
    assert [f["id"] for f in engine.finished] == [r.id for r in reqs[-3:]]
    assert "ttft_p50_s" in st and "1,024" in LLMEngine.stats.__doc__


DECODE = re.compile("decode")
PREFILL = re.compile("prefill|first_token")   # the benchmark's patterns


def _module_name(lowered):
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def _block_generation_programs():
    """The three programs of a configuration with `block_length`,
    lowered at a tiny size (the fused one at k = 8)."""
    cfg = configs.tiny_sdar_test()
    params = init_params(cfg, jax.random.key(0))
    W, S, B, Bd = 8, 16, 2, cfg.block_length
    cache = generate.init_kv_cache(cfg, B, 32)
    state = generate.init_block_state(cfg, B)
    rows, temps = jnp.zeros((W,), jnp.int32), jnp.zeros((B,), jnp.float32)
    return {
        "prefill_block_batch": generate.prefill_block_batch.lower(
            cfg, params, cache, state, jnp.zeros((W, S), jnp.int32), rows,
            rows, jnp.zeros((W, Bd), jnp.int32), jnp.ones((W, Bd), bool),
            rows, rows, jnp.zeros((W,), jnp.float32)),
        "decode_block_step": generate.decode_block_step.lower(
            cfg, params, cache, jnp.zeros((B, Bd), jnp.int32),
            jnp.zeros((B,), jnp.int32)),
        "decode_block_multi": generate.decode_block_multi.lower(
            cfg, params, cache, state, temps, 8, 0, jax.random.key(0)),
    }


def test_every_serving_program_lowers_under_its_table_name(tiny_model):
    cfg, params = tiny_model
    W, S, B = 8, 16, 2
    cache = generate.init_kv_cache(cfg, B, 32)
    key = jax.random.key(0)
    toks = jnp.zeros((W, S), jnp.int32)
    lens = jnp.ones((W,), jnp.int32)
    slots = jnp.zeros((W,), jnp.int32)
    temps = jnp.zeros((W,), jnp.float32)
    pk = jnp.zeros((cfg.n_layers, 8, cfg.n_kv_heads, cfg.head_dim),
                   cfg.dtype)
    cur, t2 = jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.float32)
    logits = jnp.zeros((B, cfg.vocab_size), jnp.float32)
    from ray_tpu.serve import llm

    args = {
        "prefill": (generate.prefill, (cfg, params, cache, toks[:1], lens[0],
                                       slots[0])),
        "prefill_sample_batch": (generate.prefill_sample_batch, (
            cfg, params, cache, toks, lens, slots, 0, temps, key)),
        "prefill_suffix_batch": (generate.prefill_suffix_batch, (
            cfg, params, cache, pk, pk, toks, lens, slots, 0, temps, key)),
        "first_token_sample": (generate.first_token_sample, (
            cfg, params, toks, lens, temps, 0, key)),
        "first_token_suffix_sample": (generate.first_token_suffix_sample, (
            cfg, params, pk, pk, toks, lens, temps, 0, key)),
        "decode_step": (generate.decode_step, (cfg, params, cache, cur)),
        "sample_batch": (llm._sample_batch, (logits, t2, key, 0)),
    }
    blocks = {"decode_multi": generate.decode_multi}
    # Where a model generates a block of positions a pass, its programs
    # stand in the decode programs' places, under their names.
    by_blocks = _block_generation_programs()
    assert set(args) | set(blocks) | set(by_blocks) \
        == set(generate.PROGRAM_NAMES)
    assert len(generate.PROGRAM_NAMES) == 11
    for key_, lowered in by_blocks.items():
        name = generate.PROGRAM_NAMES[key_].format(k=8)
        assert _module_name(lowered) == "jit_" + name
        assert bool(DECODE.search(name)) == key_.startswith("decode")
        assert bool(PREFILL.search(name)) == key_.startswith("prefill")
    names = {}
    for key_, (fn, a) in args.items():
        names[key_] = _module_name(fn.lower(*a))
        assert names[key_] == "jit_" + generate.PROGRAM_NAMES[key_]
    for key_, fn in blocks.items():
        for k in (2, 8, 64):
            name = _module_name(fn.lower(cfg, params, cache, cur, t2, k, 0,
                                         key))
            assert name == "jit_" + generate.PROGRAM_NAMES[key_].format(k=k)
            assert re.search(r"_k%d$" % k, name)
            names[f"{key_}/{k}"] = name
    assert names["decode_step"] == "jit_decode_k1"
    for key_, name in names.items():
        if key_.startswith("decode"):
            assert DECODE.search(name) and not PREFILL.search(name), name
        elif key_.startswith("sample_batch"):
            assert not DECODE.search(name) and not PREFILL.search(name)
        else:
            assert PREFILL.search(name) and not DECODE.search(name), name
    assert len(set(names.values())) == len(names)


def test_a_block_size_is_one_program_compiled_once(tiny_model):
    cfg, params = tiny_model
    fn = generate.decode_multi.program_for(4)
    assert generate.decode_multi.program_for(4) is fn
    assert generate.decode_multi.program_for(2) is not fn
    cache = generate.init_kv_cache(cfg, 2, 32)
    cur, temps = jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.float32)
    cache, toks, lps, extras = generate.decode_multi(
        cfg, params, cache, cur, temps, 4, 0, jax.random.key(1))
    assert extras == generate.Extras()       # a dense stack fills nothing
    assert toks.shape == lps.shape == (4, 2) and int(cache.seq_lens[0]) == 4


def test_the_three_flash_kernels_name_themselves_in_the_jaxpr():
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    q = jnp.zeros((1, 2048, 2, 128), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True,
                                          interpret=True))

    text = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        q, q, q))
    assert text.count("pallas_call") >= 3
    found = re.findall(r"metadata=FrozenDict\(\{'kernel': '(\w+)'\}\)", text)
    assert sorted(found) == ["flash_dkv", "flash_dq", "flash_fwd"]
    # No scope of the kernels' own: the device's event keeps the name
    # the benchmark's reader matches (`closed_call.<n>`, `shard_map.<n>`).
    assert "name=flash" not in text


def test_the_train_step_marks_forward_loss_head_and_optimizer():
    from ray_tpu.parallel import ParallelPlan, make_mesh
    from ray_tpu.train.step import init_state, make_optimizer, make_train_step

    cfg = configs.tiny_test()
    mesh = make_mesh(ParallelPlan())
    opt = make_optimizer(warmup_steps=1, total_steps=10)
    with jax.sharding.set_mesh(mesh):
        state = init_state(cfg, mesh, opt)
        toks = jnp.zeros((2, 16), jnp.int32)
        text = make_train_step(cfg, opt).lower(
            state, toks, toks, jnp.ones((2, 16), jnp.float32)
        ).as_text(debug_info=True)
    for scope in ("jvp(fwd)", "transpose(jvp(fwd))", "jvp(loss_head)",
                  "transpose(jvp(loss_head))", "/optimizer/"):
        assert scope in text, scope
