"""Actor execution state (local thread actors + process actors).

Split out of core/runtime.py (VERDICT r3 #9): the per-actor mailbox /
restart / redelivery machinery (reference:
direct_actor_transport.{h,cc}, actor_scheduling_queue.h,
gcs_actor_manager.h restart FSM). Every name is re-exported from
runtime for compatibility.
"""

from __future__ import annotations

import contextlib
import logging
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .._private.config import config
from ..observability import get_recorder, record_task_metrics
from ..util import tracing as _tracing
from .exceptions import (
    ActorDiedError,
    ObjectLostError,
    TaskCancelledError,
    TaskError,
)
from .ids import ActorID, ObjectID, TaskID
from .object_ref import ObjectRef
from .resources import TPU
from .runtime_env import applied as _renv_applied
from .runtime_support import _ctx
from .task import TaskSpec

logger = logging.getLogger("ray_tpu")

# ---------------------------------------------------------------------------
# Actors
# ---------------------------------------------------------------------------

class _ActorExit(BaseException):
    pass


class ActorState:
    """A live actor: dedicated mailbox + executor thread(s).

    Mirrors the reference's direct actor transport semantics
    (direct_actor_task_submitter.h): per-caller ordered delivery (here:
    one global FIFO mailbox), max_concurrency via a pool, async actors via
    an embedded event loop. Method exceptions are stored as error objects;
    the actor stays alive (parity with the reference)."""

    def __init__(self, rt: "Runtime", actor_id: ActorID, cls: type,
                 args, kwargs, *, node: NodeState, name: str,
                 max_concurrency: int, max_restarts: int,
                 resources: ResourceSet,
                 runtime_env: Optional[Dict[str, Any]] = None,
                 max_task_retries: int = 0,
                 concurrency_groups: Optional[Dict[str, int]] = None,
                 detached: bool = False):
        self.rt = rt
        self.actor_id = actor_id
        # Creation stamp: the outstanding-resource ledger ages actor
        # rows from it (a PENDING_CREATION stuck past the leak
        # threshold becomes a suspect; ALIVE is leak-exempt).
        self.created_at = time.time()
        # lifetime="detached": survives this driver (reference:
        # gcs_actor_manager.h detached actors); on the daemon plane the
        # hosting worker outlives the creator's connection.
        self.detached = detached
        self.cls = cls
        self.init_args = args
        self.init_kwargs = kwargs
        self.runtime_env = runtime_env
        self.node = node
        self.name = name
        self.max_concurrency = max(1, max_concurrency)
        self.max_restarts = max_restarts
        # Method calls interrupted by a restartable actor death are
        # re-delivered after the restart up to this many times
        # (reference: max_task_retries).
        self.max_task_retries = max_task_retries
        self.restarts = 0
        self.resources = resources
        self.mailbox: "queue.Queue" = queue.Queue(maxsize=config.actor_queue_max)
        # Crash-interrupted calls re-enter HERE, consumed before the
        # mailbox — redelivery must not jump behind later submissions
        # (ordered-delivery contract) and must never block (unbounded).
        self.redeliver_q: "queue.Queue" = queue.Queue()
        # Named concurrency groups: each group gets its own mailbox +
        # thread pool, so slow methods in one group don't head-of-line
        # block another (reference: concurrency_group_manager.h).
        # Thread-based actors only — a proc actor's dedicated worker is
        # one process and serializes regardless (see ProcActorState).
        self.concurrency_groups = dict(concurrency_groups or {})
        # Bounded like the main mailbox: group routing must not bypass
        # actor backpressure.
        self.group_mailboxes: Dict[str, "queue.Queue"] = {
            g: queue.Queue(maxsize=config.actor_queue_max)
            for g in self.concurrency_groups}
        self.dead = threading.Event()
        self.ready = threading.Event()
        # @method(...) per-method defaults, resolvable even when the
        # class body is not importable locally (cross-driver proxies
        # receive these from the control plane's actor table).
        self.method_defaults: Dict[str, Dict[str, Any]] = {
            m: dict(getattr(getattr(cls, m), "_ray_method_opts"))
            for m in dir(cls)
            if not m.startswith("__")
            and hasattr(getattr(cls, m, None), "_ray_method_opts")
        }
        self.death_cause: Optional[BaseException] = None
        self.instance = None
        self._death_lock = threading.Lock()
        self._death_done = False
        self.generation = 0  # bumped on restart; stale threads no-op in _die
        self._restartable_kill = False
        self._is_async = any(
            _is_coro_fn(getattr(cls, m, None)) for m in dir(cls)
            if not m.startswith("__")
        )
        self._threads: List[threading.Thread] = []
        self._start_threads()

    def _start_threads(self):
        gen = self.generation
        if self._is_async:
            t = threading.Thread(
                target=self._async_main, args=(gen,),
                name=f"actor-{self.name}", daemon=True)
            t.start()
            self._threads = [t]
        else:
            # First thread constructs the instance; extras join after ready.
            t = threading.Thread(
                target=self._sync_main, args=(True, gen),
                name=f"actor-{self.name}", daemon=True)
            t.start()
            self._threads = [t]
            for i in range(1, self.max_concurrency):
                t = threading.Thread(
                    target=self._sync_main, args=(False, gen),
                    name=f"actor-{self.name}-{i}", daemon=True)
                t.start()
                self._threads.append(t)
            for group, limit in self._group_pools().items():
                mbox = self.group_mailboxes[group]
                for i in range(limit):
                    t = threading.Thread(
                        target=self._sync_main, args=(False, gen, mbox),
                        name=f"actor-{self.name}-{group}-{i}",
                        daemon=True)
                    t.start()
                    self._threads.append(t)

    # -- lifecycle --------------------------------------------------------
    def _construct(self, gen: int) -> bool:
        try:
            with _renv_applied(self.runtime_env):
                self.instance = self.cls(*self.init_args,
                                         **self.init_kwargs)
            self.ready.set()
            return True
        except BaseException as e:  # noqa: BLE001
            self.death_cause = TaskError(self.cls.__name__ + ".__init__", e)
            self._die(gen)
            return False

    def _die(self, gen: int):
        """Called by every worker thread on loop exit. Only the first thread
        of the *current* generation performs death bookkeeping (resource
        release must happen exactly once); restart bumps the generation so
        stale threads become no-ops
        (reference restart semantics: gcs_actor_manager.h:513
        GcsActorManager::ReconstructActor)."""
        with self._death_lock:
            if gen != self.generation or self._death_done:
                return
            if self._restartable_kill and self.restarts < self.max_restarts:
                self.restarts += 1
                logger.info("Restarting actor %s (%d/%d)",
                            self.name, self.restarts, self.max_restarts)
                self._restartable_kill = False
                self.death_cause = None
                self.instance = None  # raylint: disable=unguarded-handle-teardown -- Python object, not a native handle; stale-generation worker threads no-op on the generation check before touching instance
                self.generation += 1
                self.dead.clear()
                self.ready.clear()
                self._start_threads()
                return
            self._death_done = True
        self.dead.set()
        self.ready.set()
        # Flight-recorder bookkeeping OUTSIDE the death lock (auto_dump
        # does file IO). Deliberate exits (kill / exit_actor) are
        # recorded but don't trigger a crash dump.
        cause = self.death_cause
        deliberate = isinstance(cause, ActorDiedError) and any(
            s in str(cause) for s in ("Killed via", "exit_actor"))
        rec = get_recorder()
        rec.record("scheduler", "actor_died",
                   actor=self.name or self.actor_id.hex(),
                   cause=repr(cause)[:200] if cause else "shutdown",
                   restarts=self.restarts)
        if cause is not None and not deliberate:
            rec.auto_dump("actor_died")
        # Drain all mailboxes (+ redelivery queue) with death errors.
        drains = [self.redeliver_q, self.mailbox,
                  *self.group_mailboxes.values()]
        def _next_spec():
            for q_ in drains:
                try:
                    return q_.get_nowait()
                except queue.Empty:
                    continue
            return StopIteration
        while True:
            spec = _next_spec()
            if spec is StopIteration:
                break
            if spec is not None:
                try:
                    self.rt._store_error(
                        spec,
                        self.death_cause
                        or ActorDiedError(self.actor_id.hex()),
                    )
                    self.rt._task_finished(spec)
                except BaseException as e:  # noqa: BLE001 - one bad spec
                    # must not strand the rest of the drained mailbox
                    self.rt._fail_spec_internal(spec, e)
        self.rt._on_actor_dead(self)

    def kill(self, *, no_restart: bool = True):
        self.death_cause = ActorDiedError(
            self.actor_id.hex(), "Killed via ray_tpu.kill().")
        self._restartable_kill = not no_restart
        self.dead.set()
        try:
            self.mailbox.put_nowait(None)  # wake the loop
        except queue.Full:
            pass

    def _group_pools(self) -> Dict[str, int]:
        """Groups that get dedicated threads (ProcActorState: none —
        its dedicated worker process is a single pipeline; async actors:
        none — the event loop is already concurrent and only the main
        mailbox is drained)."""
        return {} if self._is_async else self.concurrency_groups

    # Mailbox wake marker: enqueued when something lands in
    # redeliver_q so an IDLE mailbox notices immediately without the
    # loop polling. A short get-timeout looked harmless but at the 10k-
    # actor scale point 10 wakeups/s/thread saturates the host with
    # context switches before any work runs.
    _WAKE = object()

    # -- execution --------------------------------------------------------
    def _sync_main(self, constructs: bool, gen: int, mbox=None):
        _ctx.actor_id = self.actor_id
        _ctx.node_id = self.node.node_id
        if constructs:
            if not self._construct(gen):
                return
        else:
            self.ready.wait()
        own_mbox = mbox if mbox is not None else self.mailbox
        main_loop = mbox is None
        while not self.dead.is_set() and gen == self.generation:
            try:
                # Redelivered calls are drained by the main pool only.
                if not main_loop:
                    raise queue.Empty
                spec = self.redeliver_q.get_nowait()
            except queue.Empty:
                try:
                    spec = own_mbox.get(timeout=5.0)
                except queue.Empty:
                    continue
            if spec is ActorState._WAKE:
                continue
            if spec is None and not self.dead.is_set():
                # Stale kill sentinel: the previous generation exited on
                # the dead flag without consuming it and the restart
                # already cleared dead — honoring it would kill the
                # fresh generation with no cause.
                continue
            if spec is None or self.dead.is_set():
                # A real spec popped in the same race as the kill must
                # reach the death drain — breaking here would drop it
                # with its returns forever pending.
                if spec is not None:
                    self.redeliver_q.put(spec)
                    # _death_done is set BEFORE the drain runs, so if
                    # it is visible the drain may already have passed
                    # redeliver_q — drain any leftovers here (pop
                    # ownership is exclusive, so this never double-
                    # stores against the real drain).
                    with self._death_lock:
                        death_done = self._death_done
                    if death_done:
                        while True:
                            try:
                                s2 = self.redeliver_q.get_nowait()
                            except queue.Empty:
                                break
                            try:
                                self.rt._store_error(
                                    s2, self.death_cause
                                    or ActorDiedError(
                                        self.actor_id.hex()))
                                self.rt._task_finished(s2)
                            except BaseException as e:  # noqa: BLE001
                                self.rt._fail_spec_internal(s2, e)
                break
            try:
                self._run_method(spec)
            except BaseException as e:  # noqa: BLE001 - an internal bug
                # must fail THIS call, not kill the mailbox thread and
                # strand every queued call (VERDICT r4 weak #2)
                self.rt._fail_spec_internal(spec, e)
        self._die(gen)

    def _async_main(self, gen: int):
        import asyncio
        _ctx.actor_id = self.actor_id
        _ctx.node_id = self.node.node_id
        if not self._construct(gen):
            return
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        sem = asyncio.Semaphore(self.max_concurrency)

        async def runner():
            while not self.dead.is_set():
                try:
                    spec = await loop.run_in_executor(
                        None, lambda: self.mailbox.get(timeout=5.0))
                except queue.Empty:
                    continue
                if spec is ActorState._WAKE:
                    continue
                if spec is None:
                    if not self.dead.is_set():
                        continue  # stale kill sentinel (see _sync_main)
                    break

                async def run_one(s=spec):
                    async with sem:
                        try:
                            await self._run_method_async(s)
                        except BaseException as e:  # noqa: BLE001
                            self.rt._fail_spec_internal(s, e)

                loop.create_task(run_one())
            # let in-flight tasks finish
            pending = [t for t in asyncio.all_tasks(loop)
                       if t is not asyncio.current_task()]
            if pending:
                await asyncio.gather(*pending, return_exceptions=True)

        try:
            loop.run_until_complete(runner())
        finally:
            loop.close()
            self._die(gen)

    def _bind_method(self, spec: TaskSpec):
        if spec.method_name == "__ray_tpu_apply__":
            # Injected execution: first arg is a callable taking the
            # actor instance (compiled-DAG loops use this to pin a
            # driver-provided loop onto the actor; reference:
            # compiled_dag_node.py do_exec_compiled_task).
            return lambda fn, *a, **kw: fn(self.instance, *a, **kw)
        method = getattr(self.instance, spec.method_name)
        return method

    def _enter_method_trace(self, spec: TaskSpec) -> contextlib.ExitStack:
        """Lifecycle stamps + trace re-entry shared by the sync/async/
        proc method runners. Mailbox pickup = scheduled; now = running."""
        spec.timing.setdefault("scheduled", time.time())
        spec.timing["running"] = time.time()
        stack = contextlib.ExitStack()
        if spec.trace_id:
            stack.enter_context(_tracing.trace_context(
                spec.trace_id, spec.parent_span_id))
            stack.enter_context(_tracing.span(
                f"actor:{spec.display_name()}", "actor_execute",
                task_id=spec.task_id.hex(),
                actor_id=self.actor_id.hex()))
        return stack

    def _finish_method(self, spec: TaskSpec, t0: float,
                       failed: bool) -> None:
        spec.timing["finished"] = time.time()
        self.rt._task_finished(spec)
        record_task_metrics(spec.timing,
                            "FAILED" if failed else "FINISHED")
        self.rt.events.record(
            spec.display_name(), t0, time.monotonic(),
            self.node.node_id, spec.task_id.hex(),
            timing=spec.timing, trace_id=spec.trace_id,
            deps=spec.dep_ids(), returns=spec.return_hexes())

    def _run_method(self, spec: TaskSpec):
        _ctx.task_id = spec.task_id
        t0 = time.monotonic()
        failed = False
        trace_cm = self._enter_method_trace(spec)
        try:
            method = self._bind_method(spec)
            args, kwargs = self.rt._materialize_args(spec)
            with _renv_applied(self.runtime_env):
                result = method(*args, **kwargs)
            self.rt._store_results(spec, result, t0)
        except _ActorExit:
            self.rt._store_results(spec, None, t0)
            self.death_cause = ActorDiedError(
                self.actor_id.hex(), "exit_actor() was called.")
            self.dead.set()
        except BaseException as e:  # noqa: BLE001
            failed = True
            self.rt._store_error(spec, _wrap(spec, e), t0)
        finally:
            trace_cm.close()
            _ctx.task_id = None
            self._finish_method(spec, t0, failed)

    async def _run_method_async(self, spec: TaskSpec):
        _ctx.task_id = spec.task_id
        t0 = time.monotonic()
        failed = False
        trace_cm = self._enter_method_trace(spec)
        try:
            method = self._bind_method(spec)
            args, kwargs = self.rt._materialize_args(spec)
            with _renv_applied(self.runtime_env):
                result = method(*args, **kwargs)
                if hasattr(result, "__await__"):
                    result = await result
            self.rt._store_results(spec, result, t0)
        except _ActorExit:
            self.rt._store_results(spec, None, t0)
            self.death_cause = ActorDiedError(
                self.actor_id.hex(), "exit_actor() was called.")
            self.dead.set()
        except BaseException as e:  # noqa: BLE001
            failed = True
            self.rt._store_error(spec, _wrap(spec, e), t0)
        finally:
            trace_cm.close()
            _ctx.task_id = None
            self._finish_method(spec, t0, failed)


class ProcActorState(ActorState):
    """An actor hosted by a dedicated worker PROCESS (worker_proc.py).

    Reuses ActorState's mailbox/restart/death machinery; only
    construction and method execution are overridden to round-trip
    through the worker. A worker crash is an actor death that follows
    the normal max_restarts policy — the restart's _construct leases a
    fresh worker and re-runs __init__ (reference:
    gcs_actor_manager.h:513 ReconstructActor after worker failure)."""

    def __init__(self, *args, **kwargs):
        self._worker = None
        # One worker socket == one in-flight call; concurrency groups
        # stay an in-process-actor feature.
        kwargs["max_concurrency"] = 1
        super().__init__(*args, **kwargs)

    @property
    def _pool(self):
        return self.node.pool

    def _start_threads(self):
        # Always the sync mailbox loop: coroutine methods are awaited
        # worker-side (asyncio.run in worker_main).
        self._is_async = False
        super()._start_threads()

    def _construct(self, gen: int) -> bool:
        import cloudpickle

        from .worker_proc import WorkerCrashedError

        if self._worker is not None:  # restart: retire the old worker
            self._pool.retire(self._worker)
            self._worker = None
        w = None
        try:
            # A dedicated worker per actor (reference: the raylet spawns
            # a fresh worker process for every actor) — actors never
            # drain the task pool.
            w = self._pool.spawn_dedicated()
            create_msg = {
                "type": "actor_create",
                "task_id": None,
                "actor_id": self.actor_id.binary(),
                "cls": cloudpickle.dumps(self.cls),
                "args": tuple(self.rt._pack_arg(a) for a in self.init_args),
                "kwargs": {k: self.rt._pack_arg(v)
                           for k, v in self.init_kwargs.items()},
            }
            if self.runtime_env:
                create_msg["runtime_env"] = self.runtime_env
            if self.resources.get(TPU):
                create_msg["num_tpus"] = self.resources.get(TPU)
            reply = w.run_task(create_msg)
            for ev in reply.get("spans") or ():
                self.rt.events.record_raw(ev)
            if reply.get("error") is not None:
                raise self.rt._unpack_error(reply["error"])
            self._worker = w
            self.instance = w  # marker: lives remotely
            self.ready.set()
            return True
        except BaseException as e:  # noqa: BLE001
            if w is not None:
                self._pool.retire(w)
            if isinstance(e, WorkerCrashedError):
                self._restartable_kill = True  # worker death is restartable
            self.death_cause = TaskError(self.cls.__name__ + ".__init__", e)
            self._die(gen)
            return False

    def _group_pools(self) -> Dict[str, int]:
        # The dedicated worker is ONE process: group threads would race
        # on its socket for no parallelism — groups collapse into the
        # ordered mailbox (routing in submit_actor_task).
        return {}

    def _run_method(self, spec: TaskSpec):
        from .worker_proc import WorkerCrashedError

        spec.redelivered = False  # fresh delivery (incl. retry passes)
        _ctx.task_id = spec.task_id
        t0 = time.monotonic()
        failed = False
        streaming = spec.num_returns in ("streaming", "dynamic")
        gst = self.rt._generators.get(spec.task_id) if streaming else None
        trace_cm = self._enter_method_trace(spec)
        try:
            msg = {
                "type": "actor_call",
                "task_id": spec.task_id,
                "actor_id": self.actor_id.binary(),
                "method": spec.method_name,
                "args": tuple(self.rt._pack_arg(a) for a in spec.args),
                "kwargs": {k: self.rt._pack_arg(v)
                           for k, v in spec.kwargs.items()},
                "num_returns": 0 if streaming else spec.num_returns,
                "return_ids": [oid.binary() for oid in spec.return_ids],
                "streaming": streaming,
            }
            if spec.trace_id:
                msg["trace_id"] = spec.trace_id
                msg["parent_span_id"] = (_tracing.current_span_id()
                                         or spec.parent_span_id)
            if streaming and gst is not None:
                msg["backpressure"] = \
                    config.generator_backpressure_max_items
            if self.runtime_env:
                msg["runtime_env"] = self.runtime_env

            def on_stream(item):
                oid = ObjectID.for_return(spec.task_id, item["index"])
                with self.rt.lineage_lock:
                    self.rt.lineage[oid] = spec
                self.rt._store_packed(oid, item["payload"])
                if gst is not None:
                    ref = self.rt.register_ref(ObjectRef(oid))
                    with gst.cv:
                        gst.refs.append(ref)
                        gst.cv.notify_all()

            if gst is not None:
                with gst.cv:
                    gst.ack_cb = self._worker.send_ack
            try:
                reply = self._worker.run_task(
                    msg, on_stream=on_stream if streaming else None)
            finally:
                if gst is not None:
                    with gst.cv:
                        gst.ack_cb = None
            # Merge worker-side spans BEFORE the error check — failed
            # calls keep their trace.
            for ev in reply.get("spans") or ():
                self.rt.events.record_raw(ev)
            if reply.get("error") is not None:
                err = self.rt._unpack_error(reply["error"])
                if isinstance(err, _ActorExit):
                    self.rt._store_results(spec, None, t0)
                    self.death_cause = ActorDiedError(
                        self.actor_id.hex(), "exit_actor() was called.")
                    self.dead.set()
                    return
                raise err
            if streaming and gst is not None:
                with gst.cv:
                    gst.done = True
                    gst.cv.notify_all()
                self.rt._generators.pop(spec.task_id, None)
            else:
                for oid, packed in zip(spec.return_ids, reply["returns"]):
                    self.rt._store_packed(oid, packed)
        except WorkerCrashedError as e:
            left = spec.task_retries_left
            if left is None:
                left = self.max_task_retries
            will_restart = self.restarts < self.max_restarts
            get_recorder().record(
                "scheduler", "actor_worker_crashed",
                actor=self.name or self.actor_id.hex(),
                method=spec.method_name or "",
                will_restart=will_restart)
            self.death_cause = ActorDiedError(
                self.actor_id.hex(), f"worker process died: {e}")
            self._restartable_kill = True  # honor max_restarts
            # -1 = retry forever (reference max_task_retries semantics).
            # Streaming calls are NOT redelivered: their generator state
            # already holds delivered items and a rerun would duplicate
            # them for the consumer.
            if (left != 0) and will_restart and not streaming:
                # Re-deliver the interrupted call to the restarted
                # actor instead of erroring it. The task stays pending
                # (the finally must not pop it, or a concurrent get()
                # could lineage-resubmit it).
                spec.task_retries_left = left - 1 if left > 0 else left
                spec.redelivered = True
                self.redeliver_q.put(spec)
                with contextlib.suppress(queue.Full):
                    self.mailbox.put_nowait(ActorState._WAKE)
                self.dead.set()
                return
            failed = True
            self.rt._store_error(spec, _wrap(spec, e), t0)
            self.dead.set()
        except BaseException as e:  # noqa: BLE001
            failed = True
            self.rt._store_error(spec, _wrap(spec, e), t0)
        finally:
            trace_cm.close()
            _ctx.task_id = None
            if not spec.redelivered:
                self._finish_method(spec, t0, failed)

    def _die(self, gen: int):
        super()._die(gen)
        # Final death (not a restart): retire the dedicated worker.
        if self.dead.is_set() and self._worker is not None:
            w = self._worker
            self._worker = None  # raylint: disable=unguarded-handle-teardown -- lifecycle-ordered: _construct() runs before the worker loop that can reach _die(), and the null copies to a local first
            self._pool.retire(w)


def _is_coro_fn(f) -> bool:
    import inspect
    return f is not None and inspect.iscoroutinefunction(f)


def _wrap(spec: TaskSpec, e: BaseException) -> BaseException:
    if isinstance(e, (TaskError, ActorDiedError, TaskCancelledError,
                      ObjectLostError)):
        return e
    return TaskError(spec.display_name(), e)

