"""Worker process entry point.

Capability-equivalent to the reference's default_worker.py + the
CoreWorker task-execution loop (reference:
_private/workers/default_worker.py; CoreWorkerProcess::
RunTaskExecutionLoop → execute_task _raylet.pyx:1644): connect back to
the driver's socket, register, then loop executing pushed tasks. Objects
larger than the inline threshold are written to / read from the shared
C++ shm store; only ids cross the socket.

Also hosts actor instances: `actor_create` instantiates the class in
this process; subsequent `actor_call`s run its methods here, in arrival
order (the per-caller ordering the reference's actor submit queue
guarantees — there is a single caller, the driver).
"""

from __future__ import annotations

import argparse
import contextlib
import os
import socket
import sys
import traceback
from typing import Any, Dict, Optional


def _runtime_env(renv: Optional[Dict[str, Any]]):
    # Lazy import: pulling in ray_tpu.core.runtime_env at module scope
    # would run the full ray_tpu package __init__ (jax and friends) at
    # worker startup and blow the spawn-accept deadline.
    from ray_tpu.core.runtime_env import applied

    return applied(renv)


def _refuse_tpu_on_cpu_pin(msg) -> None:
    """One process owns a chip. Spawned workers default to
    JAX_PLATFORMS=cpu (worker_proc.py, cluster_utils.py) so they never
    take it from the driver — which means a task or actor that asked for
    `num_tpus` and was placed here would compute on the CPU without a
    word. Fail it instead. The native hand-off plane forwards the
    driver's frame whole ("resources"); the Python planes send
    "num_tpus"."""
    tpus = msg.get("num_tpus") or (msg.get("resources") or {}).get("TPU")
    if tpus and os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        raise RuntimeError(
            f"this task/actor asked for num_tpus={tpus:g} but was placed "
            f"in worker process {os.getpid()}, which is pinned to the CPU "
            "(JAX_PLATFORMS=cpu): it would compute on the CPU. Run it in "
            "the process that owns the chip (the driver: default local "
            "runtime, num_worker_procs=0), or start that node with "
            "JAX_PLATFORMS=tpu so that one of its workers owns it.")


def _setup(args):
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    sock.connect(args.socket)
    shm = None
    if args.shm:
        try:
            from ray_tpu._native.shm_store import ShmStore

            shm = ShmStore(args.shm, create=False)
        except Exception:  # noqa: BLE001 — shm optional; fall back inline
            shm = None
    return sock, shm


def _unpack_args(packed_args, packed_kwargs, shm, pinned=None):
    """Resolve wire args. With `pinned` (a list), shm-resident args
    deserialize ZERO-COPY — numpy values are read-only views straight
    into the arena, no GiB-scale copy on the consume path — and their
    keys are appended for the caller to shm.release() once the task
    AND its result packing are done (the pin keeps eviction off the
    span while user code can still see it). Without `pinned`, buffers
    are copied out and the pin drops immediately — actor messages use
    this, since an actor may legitimately stash an arg in its state
    long past the call."""
    from ray_tpu.core import serialization
    from ray_tpu.core.worker_proc import SerArg, ShmArg

    def resolve(v):
        if isinstance(v, (ShmArg, SerArg)):
            if isinstance(v, ShmArg):
                if shm is None:
                    raise RuntimeError("shm arg but no shm store attached")
                view = shm.get(v.key, pin=True)
                if view is None:
                    raise KeyError(v.key.hex())
                if pinned is not None:
                    pinned.append(v.key)
                    data = serialization.SerializedObject.from_bytes(
                        view, copy=False)
                    value = serialization.deserialize(data)
                else:
                    try:
                        data = serialization.SerializedObject.from_bytes(
                            view)
                        value = serialization.deserialize(data)
                    finally:
                        shm.release(v.key)
            else:
                value = serialization.deserialize(
                    serialization.SerializedObject.from_bytes(v.data))
            if v.is_error:
                raise value
            return value
        return v

    args = tuple(resolve(a) for a in packed_args)
    kwargs = {k: resolve(v) for k, v in packed_kwargs.items()}
    return args, kwargs


def _pack_value(value, shm, inline_max: int, key: bytes):
    """serialize; big payloads go to shm under `key` (the return
    ObjectID — so the driver's store/lineage see the same id), small
    payloads ship inline. Returns a wire tuple."""
    from ray_tpu.core import serialization

    data = serialization.serialize(value)
    blob = data.to_bytes()
    if shm is not None and len(blob) > inline_max:
        try:
            shm.put(key, blob)
            return ("shm", key)
        except Exception as e:  # noqa: BLE001 — store full/dup: ship inline
            # Re-executed task (lineage reconstruction): the arena may
            # already hold this key from the first run — the put fails
            # duplicate, but the shm reference is still valid.
            try:
                if shm.contains(key):
                    return ("shm", key)
            except Exception:  # noqa: BLE001 — fall through to inline
                pass
            # Inlining a large payload silently turns the transfer
            # plane into a dispatch-socket push — loud breadcrumb.
            print(f"worker: shm put of {len(blob)} B result failed "
                  f"({type(e).__name__}: {e}); shipping inline",
                  file=sys.stderr, flush=True)
    return ("ser", blob)


def _pack_error(exc: BaseException):
    from ray_tpu.core import serialization

    try:
        data = serialization.serialize(exc)
    except Exception:  # noqa: BLE001 — unpicklable exception
        data = serialization.serialize(
            RuntimeError("".join(traceback.format_exception(exc))))
    return ("ser", data.to_bytes())


# The worker's shm attachment, for components that need the store
# outside the task path (compiled-DAG channels resolve through here —
# a worker process has no global Runtime).
WORKER_SHM = None


def main() -> None:
    global WORKER_SHM
    # Cross-process lock tracing: arm BEFORE any lock is created so the
    # worker's order graph is complete. No-op unless
    # RAY_TPU_LOCKTRACE_DIR is set (see devtools/locktrace.py).
    from ray_tpu.devtools.locktrace import maybe_install_from_env

    maybe_install_from_env()
    ap = argparse.ArgumentParser()
    ap.add_argument("--socket", required=True)
    ap.add_argument("--worker-id", type=int, required=True)
    ap.add_argument("--shm", default=None)
    ap.add_argument("--inline-max", type=int, default=100 * 1024)
    args = ap.parse_args()

    from ray_tpu.core.worker_proc import recv_msg, send_msg

    sock, shm = _setup(args)
    WORKER_SHM = shm
    # Run as `python -m ...` this module is `__main__`; consumers import
    # the canonical name — publish the attachment there too.
    import ray_tpu.core.worker_main as _canonical

    _canonical.WORKER_SHM = shm
    send_msg(sock, {"type": "hello", "worker_id": args.worker_id,
                    "pid": os.getpid()})

    # Worker-side tracing: there is no Runtime in this process, so
    # finished spans buffer here and piggyback on result replies — the
    # driver merges them into its event buffer, giving `ray_tpu
    # timeline` a multi-process trace.
    from ray_tpu.util import tracing as _tracing

    _tracing.set_process_label(str(os.getpid()))
    _span_buf: list = []
    _tracing.setup_tracing(_span_buf.append)

    # Always-on low-duty-cycle profiler: retained snapshots under the
    # node's shared contprof ring (the daemon exports its resolved dir
    # via RAY_TPU_CONTPROF_DIR) so a postmortem can ask what this
    # worker was doing minutes before it died.
    try:
        from ray_tpu.observability.continuous import (
            start_continuous_profiler)

        start_continuous_profiler("worker")
    except Exception:  # noqa: BLE001 — observability must not stop boot
        pass

    def _drain_spans():
        out = list(_span_buf)
        _span_buf.clear()
        return out

    fn_cache: Dict[bytes, Any] = {}
    actors: Dict[bytes, Any] = {}

    def get_fn(msg):
        fid = msg["fid"]
        if fid not in fn_cache:
            import cloudpickle

            fn_cache[fid] = cloudpickle.loads(msg["fn"])
        return fn_cache[fid]

    # Strictly read-one/reply-one over the dedicated daemon socket:
    # one task is in flight per worker at a time. The native hand-off
    # plane (src/node_dispatch.cc) relies on this — replies carry no
    # connection tag because the loop can attribute each reply to the
    # single driver connection whose task the worker is running. Any
    # future pipelining here would need a conn-id echoed in replies.
    while True:
        msg = recv_msg(sock)
        mtype = msg.get("type")
        if mtype == "shutdown":
            return
        if mtype == "ping":
            send_msg(sock, {"type": "pong", "worker_id": args.worker_id})
            continue
        if mtype == "gen_ack":
            # Late consumption credit from a finished stream — ignore.
            continue
        if mtype == "profile":
            # On-demand stack capture for the cluster profiler: sample
            # this worker's threads for the requested duration and
            # reply terminally ("profile_result" ends the request like
            # a "result" frame does).
            from ray_tpu.observability.stack_sampler import sample_stacks

            try:
                samples = sample_stacks(
                    min(float(msg.get("duration_s") or 2.0), 60.0),
                    float(msg.get("interval_s") or 0.01))
                send_msg(sock, {"type": "profile_result",
                                "pid": os.getpid(), "samples": samples})
            except Exception as e:  # noqa: BLE001 — report, stay alive
                send_msg(sock, {"type": "profile_result",
                                "pid": os.getpid(), "samples": {},
                                "error": f"{type(e).__name__}: {e}"})
            continue

        task_id = msg.get("task_id")
        # Arena spans pinned for this message's zero-copy args —
        # released only after the result (which may serialize views of
        # those spans) is on the wire.
        pinned: list = []

        def _release_pins(pinned=pinned, shm=shm):
            while pinned:
                with contextlib.suppress(Exception):
                    shm.release(pinned.pop())

        # Re-enter the driver's trace: the outer span covers unpack +
        # user code in THIS process, parented to the driver's execute
        # span; an inner span isolates the user call itself.
        traced = msg.get("trace_id") is not None
        trace_cm = contextlib.ExitStack()
        if traced:
            trace_cm.enter_context(_tracing.trace_context(
                msg["trace_id"], msg.get("parent_span_id")))
            trace_cm.enter_context(_tracing.span(
                f"worker:{mtype}", "worker_execute",
                task_id=task_id.hex() if task_id is not None else None))

        def _run_span(label):
            return (_tracing.span(f"run:{label}", "worker_run")
                    if traced else contextlib.nullcontext())

        try:
            if mtype in ("task", "actor_create"):
                _refuse_tpu_on_cpu_pin(msg)
            if mtype == "task":
                fn = get_fn(msg)
                call_args, call_kwargs = _unpack_args(
                    msg["args"], msg["kwargs"], shm, pinned)
                with _runtime_env(msg.get("runtime_env")), \
                        _run_span(getattr(fn, "__qualname__", "task")):
                    result = fn(*call_args, **call_kwargs)
            elif mtype == "actor_create":
                import cloudpickle

                cls = cloudpickle.loads(msg["cls"])
                call_args, call_kwargs = _unpack_args(
                    msg["args"], msg["kwargs"], shm)
                with _runtime_env(msg.get("runtime_env")), \
                        _run_span(getattr(cls, "__qualname__", "actor")):
                    actors[msg["actor_id"]] = cls(*call_args, **call_kwargs)
                result = None
            elif mtype == "actor_call":
                inst = actors.get(msg["actor_id"])
                if inst is None:
                    raise RuntimeError(
                        f"actor {msg['actor_id'].hex()} not in this worker")
                if msg["method"] == "__ray_tpu_apply__":
                    # Injected-callable execution (compiled-DAG pinned
                    # loops; mirrors ActorState._bind_method).
                    def method(fn, *a, _inst=inst, **kw):
                        return fn(_inst, *a, **kw)
                else:
                    method = getattr(inst, msg["method"])
                call_args, call_kwargs = _unpack_args(
                    msg["args"], msg["kwargs"], shm)
                with _runtime_env(msg.get("runtime_env")), \
                        _run_span(msg["method"]):
                    result = method(*call_args, **call_kwargs)
            elif mtype == "actor_kill":
                actors.pop(msg["actor_id"], None)
                result = None
            else:
                raise RuntimeError(f"unknown message type {mtype!r}")
            import inspect

            if inspect.iscoroutine(result):
                import asyncio

                result = asyncio.run(result)
        except BaseException as e:  # noqa: BLE001 — user code may raise anything
            trace_cm.close()
            send_msg(sock, {"type": "result", "task_id": task_id,
                            "error": _pack_error(e),
                            "spans": _drain_spans()})
            _release_pins()
            continue
        trace_cm.close()

        streaming = msg.get("streaming", False)
        if streaming and hasattr(result, "__next__"):
            from ray_tpu.core.ids import ObjectID

            # Credit-based backpressure (reference: GeneratorWaiter,
            # core_worker.h): pause after `bp` unacknowledged items;
            # the driver grants a credit whenever the consumer takes
            # one. 0 = unbounded.
            bp = msg.get("backpressure", 0)
            inflight = 0
            i = 0
            try:
                for item in result:
                    key = ObjectID.for_return(task_id, i).binary()
                    send_msg(sock, {
                        "type": "gen_item", "task_id": task_id, "index": i,
                        "payload": _pack_value(item, shm, args.inline_max,
                                               key)})
                    i += 1
                    inflight += 1
                    while bp and inflight >= bp:
                        note = recv_msg(sock)
                        ntype = note.get("type")
                        if ntype == "gen_ack":
                            inflight -= note.get("n", 1)
                        elif ntype == "shutdown":
                            return
                        # anything else mid-stream is unexpected; skip
                send_msg(sock, {"type": "result", "task_id": task_id,
                                "error": None, "returns": [],
                                "gen_count": i, "spans": _drain_spans()})
            except BaseException as e:  # noqa: BLE001
                send_msg(sock, {"type": "result", "task_id": task_id,
                                "error": _pack_error(e), "gen_count": i,
                                "spans": _drain_spans()})
            finally:
                _release_pins()
            continue

        n = msg.get("num_returns", 1)
        return_ids = msg.get("return_ids", [])
        if n == 0 or task_id is None:
            returns = []
        elif n == 1:
            returns = [_pack_value(result, shm, args.inline_max,
                                   return_ids[0])]
        else:
            values = tuple(result)
            if len(values) != n:
                send_msg(sock, {
                    "type": "result", "task_id": task_id,
                    "error": _pack_error(ValueError(
                        f"declared num_returns={n} but returned "
                        f"{len(values)} values")),
                    "spans": _drain_spans()})
                _release_pins()
                continue
            returns = [_pack_value(v, shm, args.inline_max, return_ids[i])
                       for i, v in enumerate(values)]
        send_msg(sock, {"type": "result", "task_id": task_id,
                        "error": None, "returns": returns,
                        "spans": _drain_spans()})
        _release_pins()


if __name__ == "__main__":
    main()
