"""The per-host node daemon (raylet-equivalent).

One process per host. Owns the host's worker pool, shm object arena and
object-transfer server; registers with the control plane and heartbeats
a load report; serves task/actor dispatch over a framed-TCP protocol.

Reference capabilities mirrored (not the wire protocol):
  - src/ray/raylet/main.cc:119 — the per-node daemon composition
    (worker pool + object manager + scheduler glue).
  - src/ray/raylet/worker_pool.h:156 — spawn/cache workers (reused
    directly: core/worker_proc.WorkerPool).
  - node_manager.proto RequestWorkerLease/ReturnWorker — here the
    driver-side scheduler pushes a ready task; the daemon leases a
    worker from its pool for the task's duration.
  - ray_syncer.h:88 — load reports piggybacked on heartbeats.

Dispatch protocol (framed cloudpickle, one request in flight per
connection; drivers open a small pool of connections for parallelism):

  {"type": "task"|"actor_create"|"actor_call", ...worker msg fields...,
   "fetch": [(key, host, port), ...],   # objects to pull into local shm
   "resources": {...},                  # advisory accounting for load
   "max_calls": N, "fn": bytes|absent}
  → streaming {"type": "gen_item", ...} frames, then a terminal
    {"type": "result", ...} frame. Worker-process death is reported as
    {"type": "result", "crashed": "<why>"} so the driver can run its
    normal retry/restart machinery.
  {"type": "actor_kill", "actor_id": ...} → result
  {"type": "ping"} → {"type": "pong", "load": {...}}
  {"type": "shutdown"} → daemon exits.
"""

from __future__ import annotations

import argparse
import contextlib
import errno
import json
import logging
import os
import socket
import struct
import threading
import time
import uuid
import weakref
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

logger = logging.getLogger("ray_tpu.node")


def _load_modules():
    """Deferred heavy imports (keep daemon start fast)."""
    from ray_tpu._native import control_client as cc
    from ray_tpu._native.object_transfer import TransferClient, TransferServer
    from ray_tpu._native.shm_store import ShmStore
    from ray_tpu.core.worker_proc import (
        WorkerCrashedError,
        WorkerPool,
        recv_msg,
        send_msg,
    )

    return cc, TransferClient, TransferServer, ShmStore, WorkerPool, \
        WorkerCrashedError, recv_msg, send_msg


_FRAME = struct.Struct("!Q")  # cxx-wire: nd-frame-len


class _NdConn:
    """Socket-like reply adapter for one native-loop connection.

    Handlers write framed replies through sendall() exactly as they do
    to a real socket; the adapter strips the 8-byte length prefix (the
    C loop re-adds its own) and queues each payload on the loop's
    outbox. Raises OSError once the connection closed — the same
    signal handlers already treat as a dead driver."""

    __slots__ = ("_nd", "conn_id", "closed", "_buf")

    def __init__(self, nd, conn_id: int):
        self._nd = nd
        self.conn_id = conn_id
        self.closed = False
        self._buf = b""

    def sendall(self, data) -> None:
        if self.closed:
            raise OSError(errno.EPIPE, "native dispatch conn closed")
        self._buf += bytes(data)
        while len(self._buf) >= _FRAME.size:
            (n,) = _FRAME.unpack_from(self._buf)
            if len(self._buf) < _FRAME.size + n:
                return
            payload = self._buf[_FRAME.size:_FRAME.size + n]
            self._buf = self._buf[_FRAME.size + n:]
            if not self._nd.send(self.conn_id, payload):
                self.closed = True
                raise OSError(errno.EPIPE, "native dispatch stopped")

    def close(self) -> None:
        # The C loop owns the fd; marking closed is enough to fail
        # later writes from a handler that outlived the conn.
        self.closed = True


class NodeDaemon:
    def __init__(self, control_address: str, *,
                 node_id: Optional[str] = None,
                 num_cpus: Optional[float] = None,
                 num_tpus: Optional[float] = None,
                 resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None,
                 dispatch_port: int = 0,
                 object_port: int = 0,
                 advertise_host: str = "127.0.0.1",
                 bind_all: bool = False,
                 session_dir: Optional[str] = None,
                 shm_capacity: Optional[int] = None,
                 heartbeat_interval_s: float = 0.2):
        (cc, TransferClient, TransferServer, ShmStore, WorkerPool,
         WorkerCrashedError, recv_msg, send_msg) = _load_modules()
        self._cc_mod = cc
        self._TransferClient = TransferClient
        self._WorkerCrashedError = WorkerCrashedError
        self._recv_msg = recv_msg
        self._send_msg = send_msg

        from ray_tpu._private.config import config

        self.node_id = node_id or f"node-{uuid.uuid4().hex[:12]}"
        self.advertise_host = advertise_host
        if num_cpus is None:
            num_cpus = float(os.cpu_count() or 1)
        if num_tpus is None:
            from ray_tpu._private import accelerators

            num_tpus = float(accelerators.num_chips_per_host())
        self._stop = threading.Event()

        # Session dir for worker logs.
        if session_dir is None:
            from ray_tpu._private import session as _session

            session_dir = _session.new_session()
        self.session_dir = session_dir
        self.logs_dir = os.path.join(session_dir, "logs")
        os.makedirs(self.logs_dir, exist_ok=True)

        # Object plane: shm arena + transfer server.
        self.shm_name = f"/rtn_{self.node_id.replace('-', '')[:20]}"
        self.shm = ShmStore(
            self.shm_name,
            capacity=shm_capacity or config.object_store_memory_bytes)
        self.transfer = TransferServer(self.shm_name, object_port,
                                       bind_all=bind_all)
        from ray_tpu._native.pull_pool import PullClientPool

        self._pulls = PullClientPool(self.shm_name)

        # Continuous observability: this daemon and every worker it
        # spawns share one on-disk profile-snapshot ring (workers pick
        # the dir up via RAY_TPU_CONTPROF_DIR), and a scraper thread
        # keeps a local metrics-history window whose latest scrape
        # rides the load report to the driver.
        self.contprof_dir = (config.contprof_dir
                             or os.path.join(session_dir, "contprof"))
        self._tsdb = None
        self._contprof = None
        try:
            from ray_tpu.observability import continuous, tsdb

            if config.contprof_enabled:
                self._contprof = continuous.ContinuousProfiler(
                    "daemon", node_id=self.node_id,
                    directory=self.contprof_dir).start()
            if config.metrics_history_enabled:
                self._tsdb = tsdb.get_tsdb().start()
        except Exception:  # noqa: BLE001 — observability must not stop boot
            logger.exception("continuous observability disabled")

        # Execution plane: real OS worker processes.
        n_workers = max(1, int(num_cpus))
        worker_env = {"RAY_TPU_NODE_ID": self.node_id,
                      "RAY_TPU_CONTPROF_DIR": self.contprof_dir}
        if not num_tpus:
            # CPU-only node: set, not defaulted — an inherited
            # JAX_PLATFORMS=tpu would send a worker after a chip this
            # node does not have.
            worker_env["JAX_PLATFORMS"] = "cpu"
        self.pool = WorkerPool(n_workers, shm_name=self.shm_name,
                               logs_dir=self.logs_dir,
                               env=worker_env)

        # Resource view (advisory: the driver's scheduler owns placement;
        # this feeds the heartbeat load report for resource-view sync).
        from ray_tpu.core.resources import CPU, TPU, ResourceSet

        total = {CPU: float(num_cpus)}
        if num_tpus:
            total[TPU] = float(num_tpus)
            from ray_tpu._private import accelerators

            total.update(accelerators.pod_resources())
        total.update(resources or {})
        self.total = ResourceSet(total)
        self._avail_lock = threading.Lock()
        # Availability ledger: lives HERE (under _avail_lock) on the
        # pure-Python plane, or inside the native dispatch loop (which
        # does check-and-charge admission off the GIL) when it owns the
        # socket. All mutations go through _ledger_* so the two planes
        # cannot drift.
        self._avail_py = self.total
        self._queued = 0          # tasks waiting for a worker
        self._running = 0
        self._spilled = 0         # spillable tasks refused (stats)
        self._host_stats_cache: Dict[str, Any] = {}
        self._host_stats_ts = -1e9
        self._shm_attr_cache: Dict[str, Any] = {}
        self._shm_attr_ts = -1e9
        # Outstanding-resource ledger bookkeeping: wid -> (t0, site)
        # for workers checked out of the native registry (py-owned),
        # and pid -> first-seen stamp for shm pin holders (pin records
        # carry no timestamps; age is measured from first observation).
        self._checkouts: Dict[int, Tuple[float, str]] = {}
        self._checkouts_lock = threading.Lock()
        self._pin_first_seen: Dict[int, float] = {}
        # Peer view for spillback redirection (control-plane node table +
        # heartbeat loads), refreshed lazily on refusal.
        self._peer_view: List[dict] = []
        self._peer_view_ts = -1e9
        self._peer_view_lock = threading.Lock()

        # Actors hosted here: actor_id(bytes) ->
        # (WorkerProcess, ResourceSet, detached: bool). detached is
        # recorded LOCALLY so fencing and crash-restart decisions never
        # depend on reaching the control plane.
        self._actors: Dict[bytes, Any] = {}
        self._actors_lock = threading.Lock()
        # Running tasks (OOM-kill candidates): id -> (seq, retriable,
        # worker, label).
        self._running_tasks: Dict[int, tuple] = {}
        self._running_seq = 0
        self._running_lock = threading.Lock()
        self.memory_monitor = None
        if config.memory_monitor_threshold > 0:
            from ray_tpu.core.memory_monitor import (
                MemoryMonitor,
                usage_fn_from_config,
            )

            self.memory_monitor = MemoryMonitor(
                self._memory_victims,
                threshold=config.memory_monitor_threshold,
                interval_s=config.memory_monitor_interval_ms / 1000.0,
                usage_fn=usage_fn_from_config(),
            ).start()
        # Daemon-wide function cache: fid -> cloudpickled bytes.
        self._fn_cache: Dict[bytes, bytes] = {}
        self._fn_lock = threading.Lock()
        # Daemon-side spans (dispatch spans opened by _handle_exec)
        # buffer here and piggyback on subsequent result/pong replies,
        # mirroring worker-side span piggybacking. Only populated when
        # the daemon runs standalone (_enable_tracing from main()); an
        # in-process daemon's spans reach the driver's event buffer
        # directly through the normal _record path.
        self._span_buf: deque = deque(maxlen=2048)
        # Runtime-env materialization (the reference's per-node agent
        # role): pkg:// URIs from the control plane's KV are extracted
        # into a local size-evicted cache before tasks reach workers.
        from ray_tpu.core.runtime_env_packaging import URICache

        self._renv_cache = URICache(
            os.path.join(session_dir, "runtime_env_cache"))

        # Dispatch server: the native epoll front end
        # (src/node_dispatch.cc) owns the socket when the library is
        # built — accept, framing, admission and refusal run off the
        # GIL, and Python drains a bounded ready queue for placement
        # policy + task hand-off. RAY_TPU_NATIVE_DISPATCH=0 forces the
        # pure-Python thread-per-connection fallback (parity-testable).
        self._nd = None
        self._listener = None
        # Native-plane conn-scoped state, keyed by the loop's conn id:
        # reply adapters, actors created over a conn, live stream
        # relays (for gen_ack credit routing).
        self._nd_state_lock = threading.Lock()
        self._nd_conns: Dict[int, Any] = {}
        self._nd_conn_actors: Dict[int, list] = {}
        self._nd_streams: Dict[int, Any] = {}
        self._drainer_lock = threading.Lock()
        self._drainers: List[threading.Thread] = []
        self._drainer_busy = 0
        self._drainer_cap = max(64, 4 * n_workers)
        # Warm-path accounting: _py_exec_tasks counts tasks the PYTHON
        # plane executed (the parity suite's zero-Python assertion
        # reads it from load reports); _drainer_busy_s accumulates
        # drainer wall-time (the bench's GIL-contention proxy).
        self._py_exec_tasks = 0
        self._drainer_busy_s = 0.0
        if os.environ.get("RAY_TPU_NATIVE_DISPATCH", "1") != "0":
            try:
                from ray_tpu._native import node_dispatch as _ndmod

                if _ndmod.available():
                    self._nd = _ndmod.NativeDispatch(
                        dispatch_port, bind_all=bind_all)
            except Exception:  # noqa: BLE001 — stale .so etc.
                logger.exception(
                    "native dispatch unavailable; Python fallback")
                self._nd = None
        if self._nd is not None:
            self.dispatch_port = self._nd.port
            self._nd.set_node_id(self.node_id)
            self._nd.ledger_set(self.total.to_dict())
        else:
            self._listener = socket.socket(socket.AF_INET,
                                           socket.SOCK_STREAM)
            self._listener.setsockopt(socket.SOL_SOCKET,
                                      socket.SO_REUSEADDR, 1)
            self._listener.bind(("" if bind_all else "127.0.0.1",
                                 dispatch_port))
            self._listener.listen(128)
            self.dispatch_port = self._listener.getsockname()[1]

        # Control plane registration + heartbeats.
        host, _, port = control_address.partition(":")
        self.control = cc.ControlClient(int(port), host=host)
        meta = {
            "resources": self.total.to_dict(),
            "labels": labels or {},
            "host": advertise_host,
            "dispatch_port": self.dispatch_port,
            "object_port": self.transfer.port,
            "pid": os.getpid(),
            "session_dir": session_dir,
            "node_kind": "daemon",
        }
        self.control.register_node(self.node_id, meta=json.dumps(meta))
        # Detached-actor reconstruction (reference:
        # gcs_actor_manager.h:513 ReconstructActor — the control plane
        # owns the actor FSM cluster-wide): every daemon watches node
        # deaths; survivors race a KV claim for each detached actor the
        # dead node hosted and the winner recreates it locally from the
        # spec persisted at creation — no driver needs to be attached.
        with contextlib.suppress(Exception):
            self.control.subscribe("node_events", self._on_node_event)
        self._hb_interval = heartbeat_interval_s
        # Self-fence only AFTER the control plane has certainly
        # expired us: a fence before that kills healthy actors no
        # survivor will adopt. The timeout is the cluster operator's
        # (env, set by the launcher); default is conservative.
        try:
            cp_timeout_s = float(os.environ.get(
                "RAY_TPU_CP_HEALTH_TIMEOUT_MS", "0")) / 1000.0
        except ValueError:
            cp_timeout_s = 0.0
        self._fence_after_s = max(30.0, 3.0 * cp_timeout_s)
        self._hb_thread = threading.Thread(
            target=self._hb_loop, daemon=True, name="node-heartbeat")
        self._hb_thread.start()
        self._accept_thread = None
        if self._nd is not None:
            with contextlib.suppress(Exception):
                self._nd.set_load_report(self._load_report())
            self._push_nd_peers()
            self._nd.start()
            # Warm path: idle workers live in the C loop's registry so
            # plain tasks are forwarded straight to a worker socket with
            # zero daemon-side Python. The hooks keep pool.acquire()
            # (cold path, profiler) working transparently — a checkout
            # un-epolls the socket so Python may speak on it.
            self.pool.idle_sink = self._nd_idle_sink
            self.pool.idle_source = self._nd_idle_source
            self.pool.on_discard = self._nd_on_discard
            self._nd_seed_workers()
            # Drainer pool: grows on demand (a long-running call — an
            # actor method, a streamed task — occupies its drainer for
            # the call's duration, like the fallback's per-conn
            # threads), bounded by _drainer_cap.
            for _ in range(2):
                self._spawn_drainer()
        else:
            self._accept_thread = threading.Thread(
                target=self._accept_loop, daemon=True, name="node-accept")
            self._accept_thread.start()
        # An in-process daemon (unit harnesses, head-colocated node)
        # serves the ledger reconciler directly through a context
        # provider; a standalone daemon's context rides the heartbeat
        # instead. Weak-ref'd so a stopped daemon silently drops out.
        from ray_tpu.observability import ledger as _ledger_mod

        _self = weakref.ref(self)

        def _dispatch_ctx():
            d = _self()
            if d is None or d._stop.is_set():
                return None
            return (d._ledger_section() or {}).get("dispatch")

        _ledger_mod.register_context_provider("dispatch", _dispatch_ctx)
        logger.info("node daemon %s up: dispatch=%s:%d object=%d cpus=%s",
                    self.node_id, advertise_host, self.dispatch_port,
                    self.transfer.port, num_cpus)

    # -- load report (resource-view sync) -------------------------------
    def _host_stats(self) -> dict:
        """Host-level stats for the head's dashboard (reference:
        dashboard/agent.py per-node reporter agent). Sampled at most
        every 5s — heartbeats are far more frequent than psutil/disk
        stats need to be."""
        now = time.monotonic()
        if now - self._host_stats_ts >= 5.0:
            from ray_tpu._private.host_stats import collect_host_stats

            stats = collect_host_stats()
            try:
                stats["object_store_bytes"] = self.shm.used()
            except Exception:  # noqa: BLE001
                pass
            self._host_stats_cache = stats
            self._host_stats_ts = now
        return self._host_stats_cache

    def _shm_attribution(self) -> dict:
        """Per-process arena holdings from the slot table's pin records,
        labeled with what each pid is doing here (the daemon itself, an
        actor, a running task, an idle pool worker, or an external
        pinner). Rides the heartbeat into /api/event_stats and
        `ray_tpu status --verbose` so "who is holding the object store"
        is answerable without a debugger. Sampled on the host-stats
        cadence — the 64K-slot scan under the arena mutex is cheap but
        not heartbeat-cheap."""
        now = time.monotonic()
        if now - self._shm_attr_ts < 5.0:
            return self._shm_attr_cache
        try:
            raw = self.shm.pin_stats()
        except Exception:  # noqa: BLE001 — stats must not kill heartbeats
            return self._shm_attr_cache
        labels: Dict[int, str] = {os.getpid(): "daemon"}
        with contextlib.suppress(Exception):
            for w in self.pool.workers():
                labels.setdefault(w.pid, "worker")
        with self._actors_lock:
            for aid, entry in self._actors.items():
                labels[entry[0].pid] = f"actor:{aid.hex()}"
        with self._running_lock:
            for _seq, _retriable, worker, label in \
                    self._running_tasks.values():
                labels[worker.pid] = f"task:{label}"
        if self._nd is not None:
            # Natively handed-off tasks never enter _running_tasks;
            # label their workers from the loop's own registry so
            # shm_pins attribution stays complete on the warm path.
            with contextlib.suppress(Exception):
                for went in self._nd.workers():
                    if went.get("state") == "busy" and went.get("pid"):
                        labels[int(went["pid"])] = (
                            "task:" + str(went.get("tid") or "native"))
        holders = []
        for pid_s, rec in raw.get("pids", {}).items():
            pid = int(pid_s)
            holders.append({"pid": pid,
                            "label": labels.get(pid, "external"),
                            **rec})
        holders.sort(key=lambda h: -(h.get("pinned_bytes", 0)
                                     + h.get("creating_bytes", 0)))
        self._shm_attr_cache = {
            "pin_overflows": raw.get("pin_overflows", 0),
            "holders": holders,
        }
        self._shm_attr_ts = now
        return self._shm_attr_cache

    def _ledger_section(self) -> dict:
        """Outstanding-resource ledger entries + dispatch context for
        this node, shipped on the heartbeat load report and merged
        head-side (observability/ledger.py). Entries carry owner, age
        and acquisition site; the dispatch context carries the charge
        totals and the native py-owned worker set the reconciler
        cross-checks against the checkout records."""
        from ray_tpu.observability import ledger as _ledger

        if not config.ledger_enabled:
            return {}
        now = time.time()
        cap = max(16, int(config.ledger_max_entries_per_plane))
        # Collectors registered in THIS process (pull pool, etc.).
        entries = _ledger.local_snapshot()
        # Cold-path worker checkouts (py-owned by this daemon).
        with self._checkouts_lock:
            checkouts = list(self._checkouts.items())
        for wid, (t0, site) in checkouts[:cap]:
            entries.append(_ledger.entry(
                "dispatch.checkout", "checkout", f"co:{wid}",
                str(wid), t0, site=site, now=now))
        # Native plane: per-worker busy charges (acquire-age stamped by
        # the loop) and the authoritative py-owned set.
        handoff: Dict[str, Any] = {}
        py_owned_wids: List[int] = []
        if self._nd is not None:
            with contextlib.suppress(Exception):
                handoff = self._nd.handoff()
            with contextlib.suppress(Exception):
                for went in self._nd.workers():
                    state = went.get("state")
                    if state == "py":
                        py_owned_wids.append(int(went["wid"]))
                    elif state == "busy":
                        age = float(went.get("age_s") or 0.0)
                        entries.append(_ledger.entry(
                            "dispatch.ledger", "charge",
                            f"busy:{went['wid']}",
                            str(went.get("tid") or went["wid"]),
                            now - age,
                            site="src/node_dispatch.cc:"
                                 "start_native_task", now=now))
        # Shm pins: one entry per holding pid; a pid that no longer
        # exists flags its pins as kind="dead_pin" (the reconciler's
        # shm_pins_have_live_holders invariant). Pin records carry no
        # stamps, so age runs from first observation here.
        live_pids = set()
        for h in self._shm_attribution().get("holders", ()):
            try:
                pid = int(h.get("pid", 0))
            except (TypeError, ValueError):
                continue
            amount = (float(h.get("pinned_bytes") or 0)
                      + float(h.get("creating_bytes") or 0))
            live_pids.add(pid)
            t0 = self._pin_first_seen.setdefault(pid, now)
            kind = "pin"
            try:
                os.kill(pid, 0)
            except OSError:
                kind = "dead_pin"
            entries.append(_ledger.entry(
                "shm.pin", kind, f"pin:{pid}",
                str(h.get("label") or pid), t0,
                site=f"pid:{pid}", amount=amount, now=now))
        for pid in [p for p in self._pin_first_seen
                    if p not in live_pids]:
            del self._pin_first_seen[pid]
        avail = self.available.to_dict()
        total = self.total.to_dict()
        with self._actors_lock:
            n_actors = len(self._actors)
        disp = {
            "charged_cpu": round(total.get("CPU", 0.0)
                                 - avail.get("CPU", 0.0), 6),
            "busy": int(handoff.get("busy") or 0),
            "pending": int(handoff.get("pending") or 0),
            "py_owned": int(handoff.get("py_owned") or 0),
            "oldest_pending_s": float(
                handoff.get("oldest_pending_s") or 0.0),
            "queued": self._queued,
            "running_py": self._running,
            "actors": n_actors,
            "py_owned_wids": py_owned_wids,
        }
        return {"entries": entries[:8 * cap], "dispatch": disp}

    def _load_report(self) -> dict:
        host = self._host_stats()
        from ray_tpu.observability import event_stats as _estats

        # Per-handler loop latency (event_stats.h equivalent) rides the
        # heartbeat so the head's /api/event_stats and the
        # ray_tpu_loop_handler_* series cover every node.
        estats = _estats.snapshot()
        # Transfer-plane accounting rides the heartbeat: per-source
        # pull bytes/inflight from the pull manager plus the node's
        # serve-side counters (bytes out, relay hits) — the dashboard
        # publishes these as ray_tpu_transfer_* series.
        transfer: dict = {}
        try:
            transfer = dict(self._pulls.stats())
            transfer.update(self.transfer.stats())
        except Exception:  # noqa: BLE001 — stats must not kill heartbeats
            pass
        # Native-plane merges: the C loop times its own handlers (ping,
        # admission, refusal, reply write) off the GIL; surfacing them
        # as one more event-stats loop puts the native front end in the
        # head's /api/event_stats and the ray_tpu_loop_handler_*
        # series. Refusals it wrote natively count toward spilled.
        spilled_native = 0
        native_handoff: dict = {}
        if self._nd is not None:
            try:
                nstats = self._nd.stats()
                if nstats:
                    estats = dict(estats)
                    estats["node_dispatch_native"] = nstats
                spilled_native = self._nd.spilled()
                # Warm-path hand-off counters (workers registered with
                # the loop, tasks forwarded natively, pending depth):
                # natively-running tasks never touch _running/_queued,
                # so the load report folds them back in below.
                native_handoff = self._nd.handoff()
            except Exception:  # noqa: BLE001
                pass
        # Latest metrics scrape rides the heartbeat (one float per
        # series) so the driver's TSDB holds cluster-merged history.
        metrics_history: dict = {}
        if self._tsdb is not None:
            try:
                metrics_history = self._tsdb.latest()
            except Exception:  # noqa: BLE001 — stats must not kill heartbeats
                pass
        avail = self.available.to_dict()  # property: takes its own lock
        shm_pins = self._shm_attribution()  # takes actor/running locks
        ledger_sec: dict = {}
        try:  # takes _avail_lock via .available — stay outside it
            ledger_sec = self._ledger_section()
        except Exception:  # noqa: BLE001 — stats must not kill heartbeats
            pass
        import resource as _resource

        ru = _resource.getrusage(_resource.RUSAGE_SELF)
        with self._drainer_lock:
            drainers = {"count": len(self._drainers),
                        "busy": self._drainer_busy,
                        "busy_s_total": round(self._drainer_busy_s, 6)}
        with self._avail_lock:
            return {
                "available": avail,
                "total": self.total.to_dict(),
                "queued": (self._queued
                           + int(native_handoff.get("pending") or 0)),
                "running": (self._running
                            + int(native_handoff.get("busy") or 0)),
                "spilled": self._spilled + spilled_native,
                # Warm-path observability: py_exec_tasks is the
                # zero-Python proof counter, drainers the bench's
                # GIL-contention proxy, proc_cpu_s the per-plane CPU
                # accounting (daemon process user+sys seconds).
                "py_exec_tasks": self._py_exec_tasks,
                "drainers": drainers,
                "proc_cpu_s": round(ru.ru_utime + ru.ru_stime, 6),
                "native_handoff": native_handoff,
                "host": host,
                "event_stats": estats,
                "transfer": transfer,
                "shm_pins": shm_pins,
                "metrics_history": metrics_history,
                "ledger": ledger_sec,
            }

    def _recommend_spill_target(self, res, exclude) -> Optional[str]:
        """Pick a feasible peer for a refused task off the control-plane
        node table (reference: the raylet's cluster view backing
        retry_at_raylet_address selection, hybrid_scheduling_policy.h:50).
        Returns a node_id or None. The view is cached briefly — refusals
        are rare, but a refusal burst (many racing drivers) must not turn
        into a list_nodes stampede."""
        from ray_tpu.core.resources import ResourceSet

        exclude = set(exclude) | {self.node_id}
        now = time.monotonic()
        with self._peer_view_lock:
            if now - self._peer_view_ts > 0.5 * self._hb_interval + 0.1:
                try:
                    self._peer_view = self.control.list_nodes()
                    self._peer_view_ts = now
                except Exception:  # noqa: BLE001 — control plane briefly away
                    return None
            peers = list(self._peer_view)
        best = None
        best_score = None
        for n in peers:
            if not n.get("alive") or n.get("draining"):
                continue
            nid = n.get("node_id")
            if not nid or nid in exclude:
                continue
            try:
                load = json.loads(n["load"]) if n.get("load") else {}
            except (ValueError, TypeError):
                continue
            avail = ResourceSet(load.get("available") or {})
            if not res.fits(avail):
                continue
            # Least queued first, then most NORMALIZED headroom — raw
            # sums would let byte-denominated resources (memory) dwarf
            # CPU/TPU counts.
            total = ResourceSet(load.get("total") or {}).to_dict()
            av = avail.to_dict()
            fracs = [av.get(k, 0.0) / v for k, v in total.items() if v > 0]
            headroom = sum(fracs) / len(fracs) if fracs else 0.0
            score = (-(load.get("queued") or 0), headroom)
            if best_score is None or score > best_score:
                best, best_score = nid, score
        return best

    _hb_failures = 0

    def _hb_loop(self):
        fenced = False
        tick = 0
        while not self._stop.wait(self._hb_interval):
            tick += 1
            try:
                report = self._load_report()
                if self._nd is not None:
                    # Keep the C loop's natively-written replies (pong,
                    # refusal) carrying a fresh load report and a fresh
                    # retry_at digest — a refusal must be able to name
                    # a peer as soon as one is registered (the digest
                    # rides the cached control-plane view, so this is
                    # at most one list_nodes per refresh window).
                    with contextlib.suppress(Exception):
                        self._nd.set_load_report(report)
                    self._push_nd_peers()
                self.control.heartbeat(
                    self.node_id, load=json.dumps(report))
                self._hb_failures = 0
                fenced = False
            except Exception:  # noqa: BLE001 — control plane hiccup
                self._hb_failures += 1
                # Partitioned from the control plane long enough that
                # it has certainly declared us dead and survivors are
                # adopting our detached actors — the one-shot DEAD
                # pubsub event cannot reach us, so self-fence on the
                # heartbeat failure streak (reference: a raylet the
                # GCS declared dead stops serving).
                if (not fenced and self._hb_failures
                        * self._hb_interval > self._fence_after_s):
                    fenced = True
                    threading.Thread(target=self._fence_detached,
                                     daemon=True,
                                     name="fence-partition").start()

    # -- resource ledger (one implementation, two backing stores) -------
    @property
    def available(self):
        from ray_tpu.core.resources import ResourceSet

        if self._nd is not None:
            return ResourceSet(self._nd.ledger_available())
        with self._avail_lock:
            return self._avail_py

    def _ledger_try_charge(self, res) -> bool:
        if self._nd is not None:
            return self._nd.ledger_try_charge(res.to_dict())
        with self._avail_lock:
            if not res.fits(self._avail_py):
                return False
            self._avail_py = self._avail_py.subtract(res)
        return True

    def _ledger_charge(self, res) -> None:
        """Unconditional charge; raises ValueError when it would drive
        availability negative (ResourceSet.subtract's contract)."""
        if self._nd is not None:
            self._nd.ledger_charge(res.to_dict())
            return
        with self._avail_lock:
            self._avail_py = self._avail_py.subtract(res)

    def _ledger_release(self, res) -> None:
        if self._nd is not None:
            self._nd.ledger_release(res.to_dict())
            return
        with self._avail_lock:
            self._avail_py = self._avail_py.add(res)

    def _charge(self, res) -> None:
        self._ledger_charge(res)
        with self._avail_lock:
            self._running += 1

    def _try_charge(self, res) -> bool:
        """Atomic check-and-charge. A failed charge must be a REFUSAL
        reply, never an exception — a driver's stale view can race a
        kill's release, and unwinding the conn thread on that race
        reads as a daemon death driver-side."""
        if not self._ledger_try_charge(res):
            return False
        with self._avail_lock:
            self._running += 1
        return True

    def _uncharge(self, res) -> None:
        self._ledger_release(res)
        with self._avail_lock:
            self._running -= 1

    # -- object fetching -------------------------------------------------
    def _ensure_local(self, fetch):
        """Pull every fetch entry into the local arena. Entries are
        either the legacy (key, host, port) triple or the
        multi-location (key, [(host, port), ...]) shape — a
        fallback-ordered list of registered sources. Entries are
        DEDUPED BY KEY (a task taking the same ref twice pulls once),
        and the key is the pull-plane dedup/fairness bucket so two
        tasks wanting one object share a single transfer regardless of
        which sources each was told about.

        Returns (missing, pulled): the first key that could not be
        fetched (None when all landed) and [(key, source_ep), ...] for
        the keys that actually moved — the driver's directory registers
        this node as an additional source from them (pull_complete)."""
        seen = set()
        pulled = []
        for entry in fetch or ():
            if len(entry) == 3 and not isinstance(entry[1], (list,
                                                             tuple)):
                key, endpoints = entry[0], [(entry[1], entry[2])]
            else:
                key, endpoints = entry[0], [tuple(ep)
                                            for ep in entry[1]]
            if key in seen:
                continue
            seen.add(key)
            if self.shm.contains(key):
                continue
            try:
                src = self._pulls.pull_multi(key, endpoints, key)
                if src and src != "local":
                    pulled.append((key, src))
            except Exception:  # noqa: BLE001 — all sources gone/evicted
                if not self.shm.contains(key):
                    return key, pulled
        return None, pulled

    # -- dispatch server -------------------------------------------------
    def _accept_loop(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True, name="node-conn").start()

    # -- native dispatch plane (src/node_dispatch.cc) --------------------
    def _push_nd_peers(self) -> None:
        """Refresh the native loop's spill-target digest from the
        control plane's node table — pre-filtered (alive, non-draining,
        not self) and pre-scored (queued, normalized headroom, avail)
        so the C side's refusal path can pick retry_at without ever
        taking the GIL. Shares _recommend_spill_target's cached view so
        pushing every heartbeat doesn't stampede list_nodes."""
        if self._nd is None:
            return
        from ray_tpu.core.resources import ResourceSet

        now = time.monotonic()
        with self._peer_view_lock:
            if now - self._peer_view_ts > 0.5 * self._hb_interval + 0.1:
                try:
                    self._peer_view = self.control.list_nodes()
                    self._peer_view_ts = now
                except Exception:  # noqa: BLE001 — control plane away
                    return
            peers = list(self._peer_view)
        digest = []
        for n in peers:
            if not n.get("alive") or n.get("draining"):
                continue
            nid = n.get("node_id")
            if not nid or nid == self.node_id:
                continue
            try:
                load = json.loads(n["load"]) if n.get("load") else {}
            except (ValueError, TypeError):
                continue
            avail = load.get("available") or {}
            total = ResourceSet(load.get("total") or {}).to_dict()
            fracs = [avail.get(k, 0.0) / v
                     for k, v in total.items() if v > 0]
            headroom = sum(fracs) / len(fracs) if fracs else 0.0
            digest.append({"id": nid,
                           "queued": int(load.get("queued") or 0),
                           "headroom": headroom,
                           "avail": avail})
        with contextlib.suppress(Exception):
            self._nd.set_peers(digest)

    # -- native idle-worker registry (warm-path hand-off) ----------------
    def _nd_idle_sink(self, w) -> bool:
        """Pool hook: an idling worker's socket goes to the C loop's
        registry, making it a native hand-off target. False → the pool
        keeps the worker in its own idle queue (loop stopping, or the
        registration itself failed)."""
        nd = self._nd
        if nd is None or self._stop.is_set() or w.dedicated \
                or not w.alive:
            return False
        fids = list(w.exported_fns)
        try:
            # release() re-arms a worker the loop already holds as
            # py-owned (a cold-path checkout going back); register
            # covers first entry and re-entry after the loop dropped
            # it (worker death bookkeeping, stale-entry cleanup).
            # Either way the checkout is over — close its ledger entry.
            with self._checkouts_lock:
                self._checkouts.pop(w.worker_id, None)
            if nd.worker_release(w.worker_id, fids):
                return True
            return nd.worker_register(w.worker_id, w.sock.fileno(),
                                      w.pid, fids)
        except Exception:  # noqa: BLE001 — handle destroyed mid-stop
            return False

    def _nd_idle_source(self, timeout):
        """Pool hook: one bounded wait for an idle worker, preferring
        the native registry (the checkout un-epolls the socket so the
        caller may speak on it); falls back to the pool's own queue —
        workers land there when registration fails or the loop is
        stopping. acquire() loops on None until its deadline."""
        import queue as _q

        nd = self._nd
        slice_s = 0.2 if timeout is None else max(0.001,
                                                  min(0.2, timeout))
        if nd is not None and not self._stop.is_set():
            try:
                wid = nd.worker_acquire(timeout_ms=int(slice_s * 1000))
            except Exception:  # noqa: BLE001 — loop stopped
                wid = None
            if wid is not None:
                w = self.pool.get_worker(wid)
                if w is not None:
                    from ray_tpu.observability.ledger import (
                        acquisition_site,
                    )

                    with self._checkouts_lock:
                        self._checkouts[wid] = (time.time(),
                                                acquisition_site())
                    return w
                # Registry entry the pool no longer knows: drop it so
                # its dup'd fd cannot leak.
                with contextlib.suppress(Exception):
                    self._nd.worker_unregister(wid)
                return None
            with contextlib.suppress(_q.Empty):
                return self.pool._idle.get_nowait()
            return None
        try:
            return self.pool._idle.get(timeout=slice_s)
        except _q.Empty:
            return None

    def _nd_on_discard(self, w) -> None:
        """Pool hook: a worker leaving the pool for good must leave the
        native registry too (closes the loop's dup'd fd)."""
        nd = self._nd
        with self._checkouts_lock:
            self._checkouts.pop(w.worker_id, None)
        if nd is not None:
            with contextlib.suppress(Exception):
                nd.worker_unregister(w.worker_id)

    def _nd_seed_workers(self) -> None:
        """Move workers the pool spawned before the hooks existed from
        its idle queue into the native registry."""
        import queue as _q

        while True:
            try:
                w = self.pool._idle.get_nowait()
            except _q.Empty:
                return
            if not self._nd_idle_sink(w):
                self.pool._idle.put(w)
                return

    def _nd_worker_dead(self, wid: int) -> None:
        """The C loop saw a registered worker's socket die (EOF, or a
        failed hand-off write). The loop already released the in-flight
        task's charge and wrote the typed crashed reply; Python's job
        is pool bookkeeping — drop the corpse, respawn replacement
        capacity, and unstrand the dead process's arena pins."""
        with self._checkouts_lock:
            self._checkouts.pop(wid, None)
        w = self.pool.get_worker(wid)
        if w is not None:
            w.alive = False
            self.pool._discard(w, respawn_in_background=True)
        with contextlib.suppress(Exception):
            self.shm.reclaim_dead_pins()

    def _spawn_drainer(self) -> None:
        with self._drainer_lock:
            if (self._stop.is_set()
                    or len(self._drainers) >= self._drainer_cap):
                return
            t = threading.Thread(
                target=self._drain_loop, daemon=True,
                name=f"nd-drain-{len(self._drainers)}")
            self._drainers.append(t)
        t.start()

    def _drain_loop(self) -> None:
        """One ready-queue consumer. The pool grows on demand: a
        long-running hand-off (an actor method, a streamed task)
        occupies its drainer for the call's duration — exactly like the
        fallback's per-connection threads — so when every drainer is
        busy one more is spawned, up to _drainer_cap."""
        from ray_tpu._native import node_dispatch as _ndmod

        while not self._stop.is_set():
            try:
                ev = self._nd.next_event(timeout_ms=200)
            except StopIteration:
                return
            if ev is None:
                continue
            conn_id, kind, flags, body = ev
            if kind == _ndmod.EV_CLOSED:
                self._nd_conn_closed(conn_id)
                continue
            if kind == _ndmod.EV_WORKER_DEAD:
                # conn_id carries the worker id for this event kind.
                self._nd_worker_dead(conn_id)
                continue
            with self._drainer_lock:
                self._drainer_busy += 1
                idle = len(self._drainers) - self._drainer_busy
            t0 = time.monotonic()
            try:
                if idle <= 0:
                    self._spawn_drainer()
                self._nd_handle(conn_id, flags, body)
            finally:
                with self._drainer_lock:
                    self._drainer_busy -= 1
                    self._drainer_busy_s += time.monotonic() - t0

    def _nd_handle(self, conn_id: int, flags: int, body: bytes) -> None:
        import pickle

        from ray_tpu._native import node_dispatch as _ndmod
        from ray_tpu.observability import event_stats as _estats

        if flags & _ndmod.FLAG_JSON:
            msg = json.loads(body.decode())
            msg["_json"] = True
        elif body[:1] == b"\x01":
            (hlen,) = struct.unpack_from("<I", body, 1)  # cxx-wire: nd-hybrid-hlen
            msg = pickle.loads(body[5 + hlen:])
        else:
            msg = pickle.loads(body)
        mtype = msg.get("type")
        if mtype == "gen_ack":
            # Consumption credit for a LIVE stream: the relaying
            # drainer only reads the worker (the C loop owns the driver
            # socket), so credits are routed to the producer here.
            with self._nd_state_lock:
                worker = self._nd_streams.get(conn_id)
            if worker is not None:
                with contextlib.suppress(Exception):
                    with worker._send_lock:
                        self._send_msg(worker.sock, msg)
            return
        if flags & _ndmod.FLAG_PRECHARGED:
            msg["_nd_precharged"] = True
        with self._nd_state_lock:
            conn = self._nd_conns.get(conn_id)
            if conn is None:
                conn = _NdConn(self._nd, conn_id)
                self._nd_conns[conn_id] = conn
            actors = self._nd_conn_actors.setdefault(conn_id, [])
        try:
            with _estats.timed("node_daemon", str(mtype)):
                self._dispatch_one(conn, msg, mtype, actors)
        except (self._WorkerCrashedError, OSError, EOFError):
            pass  # conn died mid-reply; EV_CLOSED does the cleanup
        except Exception:  # noqa: BLE001 — one bad request, not a drainer
            logger.exception("native dispatch handler error (%s)", mtype)

    def _nd_conn_closed(self, conn_id: int) -> None:
        with self._nd_state_lock:
            conn = self._nd_conns.pop(conn_id, None)
            actors = self._nd_conn_actors.pop(conn_id, [])
            worker = self._nd_streams.get(conn_id)
        if conn is not None:
            conn.closed = True
        if worker is not None:
            # Driver died mid-stream: unwedge the producer (it may be
            # blocked on credits); the relaying drainer drains it back
            # to a clean pool state.
            with contextlib.suppress(Exception):
                worker.send_ack(1 << 30)
        # Driver hung up: actors created over this connection die with
        # it, same contract as the fallback's _serve_conn finally.
        for aid in actors:
            with contextlib.suppress(Exception):
                self._kill_actor(aid)

    def _recv_any(self, conn):
        """Frame decode with cross-language support: JSON frames (first
        byte '{') from non-Python clients, cloudpickle otherwise
        (reference: cross-language calls via msgpack-framed
        FunctionDescriptors, python/ray/cross_language.py — here the
        wire vocabulary is JSON, the native-friendly equivalent)."""
        import json as _json
        import struct as _struct

        from ray_tpu.core.worker_proc import _recv_exact

        header = _recv_exact(conn, 8)
        (n,) = _struct.Struct("!Q").unpack(header)
        payload = _recv_exact(conn, n)
        if payload[:1] == b"{":
            msg = _json.loads(payload.decode())
            msg["_json"] = True
            return msg
        import pickle

        if payload[:1] == b"\x01":
            # Hybrid frame (node/client.py hybrid_frame): a JSON
            # admission header for the native front end, then the
            # pickled message. The Python fallback plane admits from
            # the body's own fields, so the header is just skipped.
            (hlen,) = _struct.Struct("<I").unpack(payload[1:5])
            return pickle.loads(payload[5 + hlen:])
        return pickle.loads(payload)

    @staticmethod
    def _send_json(conn, obj) -> None:
        import json as _json
        import struct as _struct

        payload = _json.dumps(obj).encode()
        conn.sendall(_struct.Struct("!Q").pack(len(payload)) + payload)

    def _serve_conn(self, conn: socket.socket):
        """One request in flight per connection; actor connections are
        long-lived and serial, which preserves per-actor call order.
        Every dispatched message is timed into the node_daemon loop's
        event-stats registry (the event_stats.h analog)."""
        from ray_tpu.observability import event_stats as _estats

        conn_actors: list = []  # actors created over this connection
        try:
            while not self._stop.is_set():
                try:
                    msg = self._recv_any(conn)
                except (self._WorkerCrashedError, OSError, EOFError):
                    return
                mtype = msg.get("type")
                with _estats.timed("node_daemon", str(mtype)):
                    alive = self._dispatch_one(conn, msg, mtype,
                                               conn_actors)
                if not alive:
                    return
        finally:
            with contextlib.suppress(OSError):
                conn.close()
            # Driver hung up: actors created over this connection die
            # with it (the driver holds one dedicated conn per actor; a
            # deliberate kill arrives as actor_kill first).
            for aid in conn_actors:
                self._kill_actor(aid)

    def _dispatch_one(self, conn, msg, mtype, conn_actors) -> bool:
        """Handle one control-plane message. → False when this
        connection is finished (shutdown, or the conn itself died)."""
        send_msg = self._send_msg
        if mtype == "shutdown":
            self.stop()
            return False
        if mtype == "ping":
            reply = {"type": "pong", "node_id": self.node_id,
                     "load": self._load_report()}
            self._drain_spans(reply)
            if msg.get("_json"):
                self._send_json(conn, reply)
            else:
                send_msg(conn, reply)
            return True
        if mtype == "actor_kill":
            entry = self._kill_actor(msg.get("actor_id"))
            if entry is not None and len(entry) > 2 and entry[2]:
                # Explicit kill of a detached actor: drop its
                # persisted spec so no reconstruction path can
                # resurrect it (reference: GCS removes a killed
                # detached actor from the table for good).
                aid_hex = msg["actor_id"].hex()
                with contextlib.suppress(Exception):
                    self.control.kv_del("detached_spec/" + aid_hex)
            send_msg(conn, {"type": "result", "error": None,
                            "returns": []})
            return True
        if mtype == "gen_ack":
            # Late consumption credit from a finished stream.
            return True
        if mtype in ("log_list", "log_tail"):
            # Remote log flow for the head's dashboard
            # (reference: dashboard agents serving per-node
            # worker logs, dashboard/agent.py:28).
            reply = self._handle_logs(mtype, msg)
            if msg.get("_json"):
                self._send_json(conn, reply)
            else:
                send_msg(conn, reply)
            return True
        if mtype == "profile":
            # On-demand stack capture of this daemon (and its idle
            # workers) for the cluster profiler — the reference's
            # py-spy reporter path, built on sys._current_frames.
            reply = self._handle_profile(msg)
            if msg.get("_json"):
                self._send_json(conn, reply)
            else:
                send_msg(conn, reply)
            return True
        if mtype == "weight_refresh":
            # RLHF refresh prefetch: pull the published param blocks
            # into this node's arena BEFORE the generator actors'
            # refresh calls arrive — the later actor-call fetch
            # entries short-circuit on contains(), so the transfer
            # overlaps with whatever the actors are still finishing.
            # The hints carry relay-tree parents, so the prefetch wave
            # IS the broadcast tree, not a producer star.
            missing, pulled = self._ensure_local(msg.get("fetch"))
            if pulled:
                with contextlib.suppress(Exception):
                    send_msg(conn, {"type": "pull_complete",
                                    "node_id": self.node_id,
                                    "pulls": [(k, s) for k, s in pulled]})
            reply = {"type": "result",
                     "pulled": len(pulled),
                     "fetch_failed": (None if missing is None
                                      else bytes(missing).hex())}
            if msg.get("_json"):
                self._send_json(conn, reply)
            else:
                send_msg(conn, reply)
            return True
        if mtype in ("task_xlang", "actor_create_xlang",
                     "actor_call_xlang"):
            self._handle_xlang(conn, msg, conn_actors)
            return True
        if mtype in ("task", "actor_create", "actor_call"):
            try:
                self._handle_exec(conn, msg, conn_actors)
            except (self._WorkerCrashedError, OSError, EOFError):
                return False  # the connection itself is gone
            except Exception as e:  # noqa: BLE001
                # A handler bug must degrade to ONE failed
                # request, not kill this conn thread — the
                # driver reads a dead dedicated conn as a dead
                # ACTOR, and repeated conn deaths as a dead
                # NODE (cascading a single bad request into a
                # spurious cluster-membership change).
                with contextlib.suppress(Exception):
                    send_msg(conn, {
                        "type": "result",
                        "task_id": msg.get("task_id"),
                        "crashed": f"daemon handler error: "
                                   f"{type(e).__name__}: {e}"})
            return True
        reply = {"type": "result",
                 "error": f"unknown message {mtype!r}",
                 "crashed": f"unknown message {mtype!r}"}
        if msg.get("_json"):
            self._send_json(conn, reply)
        else:
            send_msg(conn, reply)
        return True

    def _handle_profile(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Sample this daemon's threads (heartbeat / accept / conn
        serving / transfer) and its idle workers for the requested
        duration; busy workers are skipped so live task traffic is
        never stalled."""
        try:
            import types

            from ray_tpu.observability import stack_sampler as _ss

            if msg.get("since_s") is not None:
                # History mode: return this node's retained
                # continuous-profiler snapshots (daemon + workers share
                # one ring dir) instead of live-sampling.
                from ray_tpu.observability import continuous

                snaps = continuous.load_snapshots(
                    since_s=float(msg["since_s"]),
                    directory=self.contprof_dir)
                return {"type": "profile_result", "ok": True,
                        "node_id": self.node_id, "snapshots": snaps}
            duration_s = min(float(msg.get("duration_s") or 2.0), 60.0)
            interval_s = float(msg.get("interval_s") or 0.01)
            out: Dict[str, Dict[str, int]] = {}
            shim = types.SimpleNamespace(worker_pool=self.pool)
            workers_t = threading.Thread(
                target=_ss._profile_local_workers,
                args=(shim, duration_s, interval_s,
                      msg.get("pid"), out),
                daemon=True)
            workers_t.start()
            out[f"daemon:{self.node_id}"] = _ss.sample_stacks(
                duration_s, interval_s)
            workers_t.join(timeout=duration_s + 10)
            return {"type": "profile_result", "ok": True,
                    "node_id": self.node_id, "processes": out}
        except Exception as e:  # noqa: BLE001 — report, don't kill conn
            return {"type": "profile_result", "ok": False,
                    "error": f"{type(e).__name__}: {e}"}

    def _drain_spans(self, reply: Dict[str, Any]) -> None:
        """Move buffered daemon-side spans onto an outgoing reply (the
        worker-span piggyback pattern): a dispatch span closes after
        its own reply went out, so it rides the next one."""
        if not self._span_buf:
            return
        spans = list(reply.get("spans") or [])
        while True:
            try:
                spans.append(self._span_buf.popleft())
            except IndexError:
                break
        if spans:
            reply["spans"] = spans

    def _enable_tracing(self) -> None:
        """Standalone-process wiring (called from main()): label spans
        as this daemon's, buffer them for reply piggybacking, and honor
        RAY_TPU_OTLP_ENDPOINT / RAY_TPU_TRACING_HOOK. Not done in
        __init__: an in-process daemon (tests) shares the driver's
        tracing globals and must not relabel or double-record them."""
        from ray_tpu.util import tracing as _tracing

        _tracing.set_process_label(f"daemon:{self.node_id}")
        _tracing.setup_tracing(self._span_buf.append)
        if self._nd is not None:
            # Standalone daemons piggyback buffered spans on pong
            # replies (_drain_spans); the C loop's GIL-free pong can't
            # carry them, so hand pings back to Python here. In-process
            # daemons never call this and keep the native fast path
            # (their span buffer stays empty).
            self._nd.set_ping_native(False)

    def _handle_logs(self, mtype: str, msg: Dict[str, Any]
                     ) -> Dict[str, Any]:
        """List / tail files under this daemon's logs dir only —
        basename-restricted so a crafted name cannot escape it."""
        try:
            if mtype == "log_list":
                files = []
                for name in sorted(os.listdir(self.logs_dir)):
                    p = os.path.join(self.logs_dir, name)
                    if os.path.isfile(p):
                        files.append({"name": name,
                                      "size": os.path.getsize(p)})
                return {"type": "result", "error": None, "files": files}
            name = os.path.basename(str(msg.get("name") or ""))
            nbytes = min(int(msg.get("nbytes") or 65536), 1 << 20)
            path = os.path.join(self.logs_dir, name)
            if not name or not os.path.isfile(path):
                return {"type": "result", "error": f"no such log {name!r}"}
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - nbytes))
                data = f.read(nbytes)
            return {"type": "result", "error": None,
                    "name": name, "size": size,
                    "data": data.decode(errors="replace")}
        except Exception as e:  # noqa: BLE001 — report, don't kill conn
            return {"type": "result", "error": f"{type(e).__name__}: {e}"}

    # -- detached-actor reconstruction ----------------------------------
    def _on_node_event(self, payload: bytes) -> None:
        text = payload.decode(errors="replace")
        state, _, nid = text.partition(":")
        if state != "DEAD":
            return
        if nid == self.node_id:
            # The control plane declared US dead (e.g. a long stall):
            # survivors are adopting our detached actors right now.
            # FENCE: kill the local copies so a false-positive death
            # cannot leave two live incarnations (reference: a raylet
            # declared dead by the GCS does not keep serving).
            threading.Thread(target=self._fence_detached,
                             daemon=True, name="fence-self").start()
            return
        threading.Thread(
            target=self._adopt_detached_from, args=(nid,),
            daemon=True, name=f"adopt-{nid}").start()

    def _fence_detached(self) -> None:
        # Decided from LOCAL state only: in the most common false-death
        # cause (a partition from the control plane) no lookup there
        # can succeed.
        with self._actors_lock:
            aids = [aid for aid, entry in self._actors.items()
                    if len(entry) > 2 and entry[2]]
        for aid in aids:
            self._kill_actor(aid)
        if aids:
            logger.warning(
                "declared DEAD by the control plane; fenced %d local "
                "detached actor copies", len(aids))

    def _adopt_detached_from(self, dead_node_id: str,
                             attempt: int = 0,
                             only_aid: Optional[str] = None) -> None:
        """Recreate the dead node's detached actors here (winner of the
        per-actor KV claim). Reference: GcsActorManager::ReconstructActor
        — restart is owned by the cluster, not by any driver."""
        import cloudpickle

        from ray_tpu._native.control_client import AlreadyExistsError

        retry = False
        try:
            actors = self.control.list_actors()
        except Exception:  # noqa: BLE001 — control plane unreachable
            return
        for a in actors:
            if a.get("state") == "DEAD":
                continue
            aid_hex = a["actor_id"]
            if only_aid is not None and aid_hex != only_aid:
                continue
            with self._actors_lock:
                if bytes.fromhex(aid_hex) in self._actors:
                    continue  # alive HERE — never restart a healthy copy
            try:
                info = self.control.get_actor(aid_hex)
                actor_meta = json.loads(info.get("meta") or "{}")
            except Exception:  # noqa: BLE001
                continue
            if not actor_meta.get("detached") \
                    or actor_meta.get("node_id") != dead_node_id:
                continue
            try:
                spec = cloudpickle.loads(
                    self.control.kv_get("detached_spec/" + aid_hex))
            except Exception:  # noqa: BLE001 — no persisted spec
                continue
            if spec.get("restarts_left", 0) <= 0:
                continue
            inc = int(actor_meta.get("incarnation", 0))
            claim = f"detached_claim/{aid_hex}/{inc}"
            try:
                self.control.kv_put(claim, self.node_id,
                                    overwrite=False)
            except AlreadyExistsError:
                continue  # another survivor won this incarnation
            except Exception:  # noqa: BLE001
                continue
            try:
                ok = self._restart_detached(aid_hex, info, actor_meta,
                                            spec, inc)
            except Exception:  # noqa: BLE001
                logger.exception("detached restart of %s failed",
                                 aid_hex[:12])
                ok = False
            if not ok:
                # Release the claim so another survivor may try — and
                # RE-RUN adoption after a delay: the one-shot DEAD
                # event has already passed every other survivor by, so
                # without a retry a failed winner (e.g. no local
                # capacity) would strand the actor forever.
                with contextlib.suppress(Exception):
                    self.control.kv_del(claim)
                retry = True
        if retry and attempt < 5 and not self._stop.is_set():
            def _later():
                time.sleep(2.0 * (attempt + 1))
                self._adopt_detached_from(dead_node_id, attempt + 1)

            threading.Thread(target=_later, daemon=True,
                             name=f"adopt-retry-{dead_node_id}").start()

    def _spawn_actor_worker(self, aid: bytes, msg: dict, res,
                            detached: bool = False) -> Tuple[Any, dict]:
        """Charge → spawn a dedicated worker → run the actor_create →
        register. Returns (worker, reply); worker is None on failure
        with EVERY side effect rolled back (a leaked charge shrinks
        this node's capacity forever). The ONE implementation of this
        sequence — the create paths (driver-submitted, reconstruction)
        must not drift on charge/retire semantics."""
        if not self._try_charge(res):
            return None, {"type": "result",
                          "task_id": msg.get("task_id"),
                          "crashed": "insufficient resources for "
                                     "actor (create raced a release; "
                                     "retry places elsewhere)"}
        worker = None
        try:
            worker = self.pool.spawn_dedicated()
            # Cross-driver calls share this worker's socket: serialize.
            worker._xlang_call_lock = threading.Lock()
            reply = worker.run_task(msg)
        except Exception as e:  # noqa: BLE001
            if worker is not None:
                with contextlib.suppress(Exception):
                    self.pool.retire(worker)
            self._uncharge(res)
            return None, {"type": "result",
                          "task_id": msg.get("task_id"),
                          "crashed": str(e)}
        if reply.get("error") is not None or reply.get("crashed"):
            with contextlib.suppress(Exception):
                self.pool.retire(worker)
            self._uncharge(res)
            return None, reply
        with self._actors_lock:
            old = self._actors.pop(aid, None)
            self._actors[aid] = (worker, res, detached)
        if old is not None:
            # Replace semantics: a concurrent recreate (driver recreate
            # racing the daemon's own crash-restart) must not leak the
            # superseded worker or its charge.
            with contextlib.suppress(Exception):
                self.pool.retire(old[0])
            self._uncharge(old[1])
        return worker, reply

    def _restart_detached(self, aid_hex: str, info: dict,
                          actor_meta: dict, spec: dict,
                          inc: int) -> bool:
        import cloudpickle

        from ray_tpu.core.resources import ResourceSet

        res = ResourceSet(spec.get("resources") or {})
        aid = bytes.fromhex(aid_hex)
        msg = {
            "type": "actor_create", "task_id": None,
            "num_returns": 0,
            "actor_id": aid,
            "cls": spec["cls"],
            "args": cloudpickle.loads(spec["args"]),
            "kwargs": cloudpickle.loads(spec["kwargs"]),
        }
        if spec.get("runtime_env"):
            from ray_tpu.core.runtime_env_packaging import (
                KV_PREFIX,
                materialize,
            )

            try:
                msg["runtime_env"] = materialize(
                    spec["runtime_env"], self._renv_cache,
                    lambda uri: self.control.kv_get(KV_PREFIX + uri))
            except Exception as e:  # noqa: BLE001
                logger.info("detached reconstruct of %s: runtime_env "
                            "setup failed: %s", aid_hex[:12], e)
                return False
        worker, reply = self._spawn_actor_worker(aid, msg, res,
                                                 detached=True)
        if worker is None:
            logger.info("detached reconstruct of %s failed: %s",
                        aid_hex[:12],
                        reply.get("crashed") or reply.get("error"))
            return False
        spec["restarts_left"] = int(spec["restarts_left"]) - 1
        with contextlib.suppress(Exception):
            self.control.kv_put("detached_spec/" + aid_hex,
                                cloudpickle.dumps(spec), overwrite=True)
        actor_meta["node_id"] = self.node_id
        actor_meta["incarnation"] = inc + 1
        # The table update is what makes the reconstruction REACHABLE
        # (drivers re-attach by reading it) — retry hard rather than
        # leaving a live-but-undiscoverable actor behind a one-shot
        # network hiccup.
        updated = False
        for _ in range(5):
            try:
                self.control.register_actor(
                    aid_hex, name=info.get("name") or "",
                    meta=json.dumps(actor_meta))
                self.control.update_actor(aid_hex, "ALIVE")
                updated = True
                break
            except Exception:  # noqa: BLE001
                time.sleep(1.0)
        if not updated:
            logger.error(
                "reconstructed detached actor %s but could not update "
                "the actor table; it is running here (%s) but "
                "undiscoverable until the table is refreshed",
                aid_hex[:12], self.node_id)
        logger.info("reconstructed detached actor %s (incarnation %d)",
                    aid_hex[:12], inc + 1)
        return True

    def _kill_actor(self, aid):
        if aid is None:
            return None
        with self._actors_lock:
            entry = self._actors.pop(aid, None)
        if entry is not None:
            w, res = entry[0], entry[1]
            self.pool.retire(w)
            self._uncharge(res)
            with contextlib.suppress(Exception):
                self.shm.reclaim_dead_pins()
        return entry

    def _handle_exec(self, conn, msg: Dict[str, Any], conn_actors) -> None:
        from ray_tpu.core.resources import ResourceSet

        send_msg = self._send_msg
        mtype = msg.pop("type")
        fetch = msg.pop("fetch", None)
        res = ResourceSet(msg.pop("resources", None) or {})
        if res.get("TPU"):
            # worker_main refuses it in a CPU-pinned worker.
            msg["num_tpus"] = res.get("TPU")
        max_calls = msg.pop("max_calls", 0)
        retriable = msg.pop("retriable", False)
        spillable = msg.pop("spillable", False)
        spill_exclude = msg.pop("spill_exclude", None) or []
        fn_bytes = msg.pop("fn", None)
        fid = msg.get("fid")
        if fn_bytes is not None and fid is not None:
            with self._fn_lock:
                self._fn_cache[fid] = fn_bytes

        # Spillback (reference: RequestWorkerLease replying with a
        # spillback address, node_manager.proto:365-379): a saturated
        # daemon REFUSES a spillable task instead of queueing it — with
        # several drivers, each one's view is heartbeat-stale and two
        # can race the same free slot; the loser's task would sit here
        # behind the winner's while another node idles. Admission is an
        # atomic check-and-charge; the reply carries the authoritative
        # load so the driver corrects its view before rescheduling.
        # Only driver-marked spillable tasks (free placement, no PG
        # reservation / node affinity) are refused. The check runs
        # BEFORE arg fetch / runtime_env setup: a refusal must not pull
        # payloads into (or build envs on) the node that won't run the
        # task. The reservation holds no _running/_queued count yet —
        # _run_task takes those over (no double-counting in the load
        # report while the task waits for a worker).
        # The native front end may have ALREADY charged admission (the
        # C loop's check-and-charge, flagged through the ready queue as
        # FLAG_PRECHARGED → _nd_precharged); a natively-refused task
        # never reaches this method at all.
        precharged = bool(msg.pop("_nd_precharged", False))
        if (not precharged and mtype == "task" and spillable
                and not res.is_empty()):
            ok = self._ledger_try_charge(res)
            if not ok:
                with self._avail_lock:
                    self._spilled += 1
                # Refuse WITH a redirect (reference: the spillback reply's
                # retry_at_raylet_address, node_manager.proto:365-379): this
                # daemon names a feasible peer off its OWN control-plane
                # view — usually fresher than the refused driver's, and the
                # exclude list prevents refusal ping-pong.
                send_msg(conn, {"type": "result",
                                "task_id": msg.get("task_id"),
                                "spillback": True,
                                "retry_at": self._recommend_spill_target(
                                    res, set(spill_exclude)),
                                "load": self._load_report()})
                return
            precharged = True

        def unreserve():
            self._ledger_release(res)

        missing, pulled = self._ensure_local(fetch)
        if missing is not None:
            if precharged:
                unreserve()
            send_msg(conn, {"type": "result", "task_id": msg.get("task_id"),
                            "fetch_failed": missing})
            return
        if pulled:
            # Multi-location directory feedback (reference:
            # OwnershipBasedObjectDirectory location updates): report
            # completed pulls on the dispatch socket so the driver
            # registers this node as an additional source for those
            # objects — later consumers spread across holders instead
            # of starring the producer. Streamed like gen_item frames;
            # the client loop consumes it before the terminal reply.
            with contextlib.suppress(Exception):
                send_msg(conn, {"type": "pull_complete",
                                "node_id": self.node_id,
                                "pulls": [(k, s) for k, s in pulled]})

        if msg.get("runtime_env"):
            from ray_tpu.core.runtime_env_packaging import (
                KV_PREFIX,
                materialize,
            )

            try:
                msg["runtime_env"] = materialize(
                    msg["runtime_env"], self._renv_cache,
                    lambda uri: self.control.kv_get(KV_PREFIX + uri))
            except Exception as e:  # noqa: BLE001 — bad/missing package
                if precharged:
                    unreserve()
                send_msg(conn, {"type": "result",
                                "task_id": msg.get("task_id"),
                                "crashed": f"runtime_env setup failed: "
                                           f"{e}"})
                return

        msg["type"] = mtype
        # Control-plane trace propagation (closes the ROADMAP gap): the
        # driver stamped trace_id/parent_span_id into the socket msg;
        # re-enter that trace here and interpose a daemon dispatch span
        # so the tree reads submit → daemon:<type> → worker execution.
        # The span closes after the reply went out; it reaches the
        # driver on the NEXT reply via _drain_spans, or the OTLP
        # exporter directly.
        with contextlib.ExitStack() as trace_cm:
            if msg.get("trace_id") is not None:
                from ray_tpu.util import tracing as _tracing

                trace_cm.enter_context(_tracing.trace_context(
                    msg.get("trace_id"), msg.get("parent_span_id")))
                sid = trace_cm.enter_context(_tracing.span(
                    f"daemon:{mtype}", "daemon_dispatch",
                    node_id=self.node_id))
                msg["parent_span_id"] = sid
            if mtype == "actor_call":
                self._run_actor_call(conn, msg)
                return
            if mtype == "actor_create":
                self._run_actor_create(conn, msg, res, conn_actors)
                return
            self._run_task(conn, msg, res, max_calls, fid, retriable,
                           precharged=precharged)

    def _memory_victims(self):
        with self._running_lock:
            entries = list(self._running_tasks.items())
        out = []
        for run_key, (seq, retriable, worker, label) in entries:

            def kill(run_key=run_key, worker=worker):
                # Re-validate under the lock: between the snapshot and
                # this kill the task may have finished and the worker
                # been re-leased to an innocent task.
                with self._running_lock:
                    cur = self._running_tasks.get(run_key)
                    if cur is None or cur[2] is not worker:
                        return
                    worker.kill()

            out.append((seq, retriable, kill, label))
        return out

    # -- cross-language execution (C++ clients) --------------------------
    def _handle_xlang(self, conn, msg, conn_actors) -> None:
        """Tasks/actors submitted by NON-Python clients: a qualified
        Python name + JSON args over JSON frames (the C++ worker API's
        task-submission surface — reference capability: cpp/ worker
        submitting cross-language tasks by FunctionDescriptor). Results
        are JSON; errors come back as {"error": ...}."""
        import cloudpickle

        mtype = msg["type"]
        try:
            if mtype == "task_xlang":
                result = self._xlang_task(msg)
            elif mtype == "actor_create_xlang":
                result = self._xlang_actor_create(msg, conn_actors)
            else:
                result = self._xlang_actor_call(msg)
            # "error" FIRST: the C++ client's flat JSON scan relies on
            # the top-level key appearing before any same-named key
            # nested inside the result value.
            self._send_json(conn, {"type": "result", "error": None,
                                   "result": result})
        except Exception as e:  # noqa: BLE001 — report, don't kill conn
            self._send_json(conn, {"type": "result",
                                   "error": f"{type(e).__name__}: {e}"})

    def _xlang_fid_and_msg(self, qualname: str, json_args: str):
        import cloudpickle

        def shim(qn, ja):
            import importlib
            import json as _j

            mod, _, fn = qn.rpartition(".")
            f = getattr(importlib.import_module(mod), fn)
            a = _j.loads(ja) if ja else []
            out = f(**a) if isinstance(a, dict) else f(*a)
            return _j.dumps(out)

        fid = b"_xlang_task_shim_" + b"0" * 11  # stable per daemon
        with self._fn_lock:
            if fid not in self._fn_cache:
                self._fn_cache[fid] = cloudpickle.dumps(shim)
        rid = os.urandom(28)
        return {
            "type": "task", "task_id": rid, "fid": fid,
            "args": (qualname, json_args), "kwargs": {},
            "num_returns": 1, "return_ids": [rid], "streaming": False,
        }, rid

    def _unpack_worker_json(self, packed) -> Any:
        """Worker return of the shim's json.dumps string → value."""
        import json as _json

        from ray_tpu.core import serialization

        kind, payload = packed
        if kind == "shm":
            view = self.shm.get(payload, pin=True)
            try:
                data = serialization.SerializedObject.from_bytes(view)
                text = serialization.deserialize(data)
            finally:
                self.shm.release(payload)
            self.shm.delete(payload)
        else:
            text = serialization.deserialize(
                serialization.SerializedObject.from_bytes(payload))
        return _json.loads(text)

    def _xlang_task(self, msg) -> Any:
        wmsg, _rid = self._xlang_fid_and_msg(
            msg["qualname"], msg.get("args_json", ""))
        worker = self.pool.acquire(timeout=300)
        try:
            if not self._inject_fn(None, wmsg, worker):
                raise RuntimeError("xlang shim missing")
            reply = worker.run_task(wmsg)
            worker.exported_fns.add(wmsg["fid"])
            if reply.get("error") is not None:
                from ray_tpu.core import serialization

                raise serialization.deserialize(
                    serialization.SerializedObject.from_bytes(
                        reply["error"][1]))
            return self._unpack_worker_json(reply["returns"][0])
        finally:
            self.pool.release(worker)

    class _XlangActorShim:
        def __init__(self, qualname, json_args):
            import importlib
            import json as _j

            mod, _, cls = qualname.rpartition(".")
            c = getattr(importlib.import_module(mod), cls)
            a = _j.loads(json_args) if json_args else []
            self.inst = c(**a) if isinstance(a, dict) else c(*a)

        def call(self, method, json_args):
            import json as _j

            a = _j.loads(json_args) if json_args else []
            m = getattr(self.inst, method)
            out = m(**a) if isinstance(a, dict) else m(*a)
            return _j.dumps(out)

    def _xlang_actor_create(self, msg, conn_actors) -> str:
        import cloudpickle

        aid = os.urandom(16)
        worker = self.pool.spawn_dedicated()
        worker._xlang_call_lock = threading.Lock()
        reply = worker.run_task({
            "type": "actor_create", "task_id": None,
            "actor_id": aid,
            "cls": cloudpickle.dumps(NodeDaemon._XlangActorShim),
            "args": (msg["qualname"], msg.get("args_json", "")),
            "kwargs": {},
        })
        if reply.get("error") is not None:
            self.pool.retire(worker)
            from ray_tpu.core import serialization

            raise serialization.deserialize(
                serialization.SerializedObject.from_bytes(
                    reply["error"][1]))
        from ray_tpu.core.resources import ResourceSet

        with self._actors_lock:
            self._actors[aid] = (worker, ResourceSet({}), False)
        conn_actors.append(aid)
        return aid.hex()

    def _xlang_actor_call(self, msg) -> Any:
        aid = bytes.fromhex(msg["actor_id"])
        with self._actors_lock:
            entry = self._actors.get(aid)
        if entry is None:
            raise KeyError("actor not hosted on this node")
        worker = entry[0]
        rid = os.urandom(28)
        # Any connection may address this actor by id: serialize the
        # socket round trip per worker or two daemon threads interleave
        # reads of one reply stream.
        lock = getattr(worker, "_xlang_call_lock", None)
        ctx = lock if lock is not None else contextlib.nullcontext()
        with ctx:
            reply = worker.run_task({
                "type": "actor_call", "task_id": rid, "actor_id": aid,
                "method": "call",
                "args": (msg["method"], msg.get("args_json", "")),
                "kwargs": {}, "num_returns": 1, "return_ids": [rid],
                "streaming": False,
            })
        if reply.get("error") is not None:
            from ray_tpu.core import serialization

            raise serialization.deserialize(
                serialization.SerializedObject.from_bytes(
                    reply["error"][1]))
        return self._unpack_worker_json(reply["returns"][0])

    def _inject_fn(self, conn, msg, worker) -> bool:
        """Ensure the worker has the function body; True = ok."""
        fid = msg.get("fid")
        if fid is None or fid in worker.exported_fns:
            msg.pop("fn", None)
            return True
        with self._fn_lock:
            fn_bytes = self._fn_cache.get(fid)
        if fn_bytes is None:
            self._send_msg(conn, {
                "type": "result", "task_id": msg.get("task_id"),
                "need_fn": True})
            return False
        msg["fn"] = fn_bytes
        return True

    def _relay_streaming(self, conn, worker, msg) -> None:
        """Bidirectional relay for a streaming task: gen_item frames
        flow worker→driver, gen_ack credits flow driver→worker
        (generator backpressure), until the worker's terminal result.
        Raises WorkerCrashedError on worker death."""
        import selectors

        if isinstance(conn, _NdConn):
            self._relay_streaming_native(conn, worker, msg)
            return
        recv_msg, send_msg = self._recv_msg, self._send_msg
        with worker._send_lock:
            send_msg(worker.sock, msg)
        def drain_worker(last_reply) -> None:
            # Driver hung up mid-stream: unwedge the worker (it may be
            # waiting on credits) and drain it to a clean state so it
            # can safely re-enter the pool.
            worker.send_ack(1 << 30)
            reply = last_reply
            while reply is None or reply.get("type") != "result":
                reply = recv_msg(worker.sock)

        sel = selectors.DefaultSelector()
        sel.register(worker.sock, selectors.EVENT_READ, "worker")
        sel.register(conn, selectors.EVENT_READ, "driver")
        try:
            while True:
                for key, _ in sel.select():
                    if key.data == "worker":
                        reply = recv_msg(worker.sock)  # raises on crash
                        try:
                            send_msg(conn, reply)
                        except OSError:
                            drain_worker(reply)
                            return
                        if reply.get("type") == "result":
                            return
                    else:
                        try:
                            note = recv_msg(conn)
                        except (self._WorkerCrashedError, OSError):
                            # DRIVER died (recv_msg raises the same
                            # error type for any socket EOF) — this is
                            # not a worker crash: drain the worker and
                            # hand it back clean.
                            sel.unregister(conn)
                            drain_worker(None)
                            return
                        if note.get("type") == "gen_ack":
                            with worker._send_lock:
                                send_msg(worker.sock, note)
        finally:
            sel.close()

    def _relay_streaming_native(self, conn, worker, msg) -> None:
        """Native-plane stream relay. The C loop owns the driver
        socket, so gen_ack credits arrive as ready-queue events on
        OTHER drainers — _nd_handle routes them to this worker through
        _nd_streams. This thread only reads the worker and forwards
        its frames; a closed driver conn (the adapter raises, or the
        EV_CLOSED handler pre-unwedged) turns into a drain-to-terminal
        so the worker re-enters the pool clean."""
        recv_msg, send_msg = self._recv_msg, self._send_msg
        with self._nd_state_lock:
            self._nd_streams[conn.conn_id] = worker
        try:
            with worker._send_lock:
                send_msg(worker.sock, msg)
            while True:
                reply = recv_msg(worker.sock)  # raises on worker crash
                try:
                    send_msg(conn, reply)
                except OSError:
                    worker.send_ack(1 << 30)
                    while reply.get("type") != "result":
                        reply = recv_msg(worker.sock)
                    return
                if reply.get("type") == "result":
                    return
        finally:
            with self._nd_state_lock:
                self._nd_streams.pop(conn.conn_id, None)

    def _run_task(self, conn, msg, res, max_calls, fid,
                  retriable: bool = False,
                  precharged: bool = False) -> None:
        send_msg = self._send_msg
        with self._avail_lock:
            self._queued += 1
            # Warm-path proof: every task the PYTHON plane executes
            # bumps this; the parity suite submits plain tasks under
            # native dispatch and asserts it stays frozen.
            self._py_exec_tasks += 1
        worker = None
        try:
            worker = self.pool.acquire(timeout=300)
        except Exception as e:  # noqa: BLE001 — pool exhausted/shutdown
            with self._avail_lock:
                self._queued -= 1
            if precharged:
                self._ledger_release(res)
            send_msg(conn, {"type": "result",
                            "task_id": msg.get("task_id"),
                            "crashed": f"no worker available: {e}"})
            return
        with self._avail_lock:
            self._queued -= 1
        if precharged:
            # Admission already reserved the resources; only the
            # running count starts now (a precharged task waiting in
            # pool.acquire must not show as running in load reports).
            with self._avail_lock:
                self._running += 1
        else:
            self._charge(res)
        with self._running_lock:
            self._running_seq += 1
            run_key = self._running_seq
            tid = msg.get("task_id")
            self._running_tasks[run_key] = (
                run_key, retriable and not msg.get("streaming"), worker,
                tid.hex() if isinstance(tid, bytes) and tid else "task")
        charged = True

        def done():
            # Return the charge BEFORE the result reply goes out: the
            # driver reacts to the reply instantly (release → dispatch
            # the next task here), and an admission check racing the
            # finally block would spuriously refuse a free node.
            nonlocal charged
            if not charged:
                return
            charged = False
            with self._running_lock:
                self._running_tasks.pop(run_key, None)
            self._uncharge(res)

        ran = False
        try:
            if msg.get("task_id") is None:
                msg["task_id"] = b""
            if not self._inject_fn(conn, msg, worker):
                return
            ran = True
            if msg.get("streaming"):
                self._relay_streaming(conn, worker, msg)
                done()
            else:
                reply = worker.run_task(
                    msg, on_stream=lambda item: send_msg(conn, item))
                done()
                self._drain_spans(reply)
                send_msg(conn, reply)
            if fid is not None:
                worker.exported_fns.add(fid)
        except self._WorkerCrashedError as e:
            done()
            # The dead worker's read pins must not strand arena
            # capacity (reference: plasma client-disconnect cleanup).
            with contextlib.suppress(Exception):
                self.shm.reclaim_dead_pins()
            with contextlib.suppress(Exception):
                send_msg(conn, {"type": "result",
                                "task_id": msg.get("task_id"),
                                "crashed": str(e)})
        finally:
            done()
            if worker is not None:
                if ran and fid is not None and max_calls > 0:
                    worker.fn_calls[fid] = worker.fn_calls.get(fid, 0) + 1
                    if worker.fn_calls[fid] >= max_calls:
                        self.pool.recycle(worker)
                        return
                self.pool.release(worker)

    def _run_actor_create(self, conn, msg, res, conn_actors) -> None:
        aid = msg["actor_id"]
        # Detached actors (reference: lifetime="detached",
        # gcs_actor_manager.h) outlive their creator's connection — any
        # driver may address them later via the control plane's actor
        # table; they die only on explicit actor_kill or daemon stop.
        detached = bool(msg.pop("detached", False))
        worker, reply = self._spawn_actor_worker(aid, msg, res, detached)
        if worker is not None and not detached:
            conn_actors.append(aid)
        with contextlib.suppress(Exception):
            self._send_msg(conn, reply)

    def _run_actor_call(self, conn, msg) -> None:
        send_msg = self._send_msg
        aid = msg["actor_id"]
        with self._actors_lock:
            entry = self._actors.get(aid)
        if entry is None:
            send_msg(conn, {"type": "result", "task_id": msg.get("task_id"),
                            "crashed": "actor not hosted on this node"})
            return
        worker = entry[0]
        # Cross-driver/detached actors can be addressed from several
        # connections; one worker socket carries one request at a time.
        lock = getattr(worker, "_xlang_call_lock", None)
        ctx = lock if lock is not None else contextlib.nullcontext()
        try:
            with ctx:
                if msg.get("streaming"):
                    self._relay_streaming(conn, worker, msg)
                else:
                    reply = worker.run_task(
                        msg, on_stream=lambda item: send_msg(conn, item))
                    self._drain_spans(reply)
                    send_msg(conn, reply)
        except self._WorkerCrashedError as e:
            was_detached = len(entry) > 2 and entry[2]
            self._kill_actor(aid)
            if was_detached:
                # Worker crash with the NODE alive: nobody publishes a
                # death event, so the cluster reconstruction path never
                # fires — this daemon restarts its own detached actor
                # from the spec (budget still enforced via the claim).
                crashed_hex = aid.hex()

                def _local_adopt():
                    time.sleep(1.0)  # let an explicit kill's DEAD land
                    self._adopt_detached_from(self.node_id,
                                              only_aid=crashed_hex)

                threading.Thread(target=_local_adopt, daemon=True,
                                 name="adopt-local-crash").start()
            with contextlib.suppress(Exception):
                send_msg(conn, {"type": "result",
                                "task_id": msg.get("task_id"),
                                "crashed": str(e)})

    # -- lifecycle --------------------------------------------------------
    def run_forever(self) -> None:
        try:
            while not self._stop.wait(0.5):
                pass
        except KeyboardInterrupt:
            pass
        finally:
            self.stop()

    def stop(self) -> None:
        if self._stop.is_set():
            return
        self._stop.set()
        if self._contprof is not None:
            with contextlib.suppress(Exception):
                self._contprof.stop()
        if self._tsdb is not None:
            with contextlib.suppress(Exception):
                self._tsdb.stop()
        if self.memory_monitor is not None:
            self.memory_monitor.stop()
        if self._nd is not None:
            # Stop the C loop first: in-flight conns close, nd_next
            # returns "stopped" and the drainer pool exits.
            with contextlib.suppress(Exception):
                self._nd.stop()
        if self._listener is not None:
            with contextlib.suppress(OSError):
                self._listener.close()
        with self._actors_lock:
            actors = list(self._actors.values())
            self._actors.clear()
        for entry in actors:
            w = entry[0]
            with contextlib.suppress(Exception):
                self.pool.retire(w)
        self.pool.shutdown()
        self._pulls.close()
        with contextlib.suppress(Exception):
            self.transfer.stop()
        with contextlib.suppress(Exception):
            self.shm.close()
        # Unlink the arena — a daemon-sized /dev/shm segment must not
        # outlive the daemon (Runtime.shutdown does the same).
        with contextlib.suppress(Exception):
            from ray_tpu._native.shm_store import ShmStore

            ShmStore.unlink(self.shm_name)
        with contextlib.suppress(Exception):
            self.control.close()
        if self._nd is not None:
            # Free the native handle only once every drainer has left
            # nd_next. stop() can be CALLED from a drainer (a wire
            # "shutdown" message) — that thread is skipped, and if any
            # drainer is still inside a hand-off after the deadline the
            # handle is leaked rather than freed under a live reader
            # (the process is exiting anyway).
            cur = threading.current_thread()
            with self._drainer_lock:
                drainers = list(self._drainers)
            deadline = time.monotonic() + 5.0
            all_joined = True
            for t in drainers:
                if t is cur:
                    all_joined = False
                    continue
                t.join(timeout=max(0.0, deadline - time.monotonic()))
                if t.is_alive():
                    all_joined = False
            if all_joined:
                with contextlib.suppress(Exception):
                    self._nd.destroy()
        # Last daemon spans must not die in the OTLP batch buffer.
        with contextlib.suppress(Exception):
            from ray_tpu.util.tracing import flush_otlp

            flush_otlp()


def main() -> None:
    # Cross-process lock tracing: arm BEFORE the daemon (and its locks)
    # exist. No-op unless RAY_TPU_LOCKTRACE_DIR is set.
    from ray_tpu.devtools.locktrace import maybe_install_from_env

    maybe_install_from_env()
    # SIGUSR1 → thread dump on stderr (live-debugging a wedged daemon).
    import faulthandler
    import signal

    with contextlib.suppress(Exception):
        faulthandler.register(signal.SIGUSR1)
    ap = argparse.ArgumentParser(description="ray_tpu node daemon")
    ap.add_argument("--address", required=True,
                    help="control plane host:port")
    ap.add_argument("--node-id", default=None)
    ap.add_argument("--num-cpus", type=float, default=None)
    ap.add_argument("--num-tpus", type=float, default=None)
    ap.add_argument("--resources", default=None, help="JSON dict")
    ap.add_argument("--labels", default=None, help="JSON dict")
    ap.add_argument("--dispatch-port", type=int, default=0)
    ap.add_argument("--object-port", type=int, default=0)
    ap.add_argument("--advertise-host", default="127.0.0.1")
    ap.add_argument("--bind-all", action="store_true")
    ap.add_argument("--session-dir", default=None)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    daemon = NodeDaemon(
        args.address,
        node_id=args.node_id,
        num_cpus=args.num_cpus,
        num_tpus=args.num_tpus,
        resources=json.loads(args.resources) if args.resources else None,
        labels=json.loads(args.labels) if args.labels else None,
        dispatch_port=args.dispatch_port,
        object_port=args.object_port,
        advertise_host=args.advertise_host,
        bind_all=args.bind_all,
        session_dir=args.session_dir,
    )
    daemon._enable_tracing()
    # Graceful SIGTERM (`ray-tpu stop`): run stop() so the shm arena is
    # unlinked and workers are torn down.
    import signal
    import sys

    def _on_term(_sig, _frm):
        daemon.stop()
        sys.exit(0)

    signal.signal(signal.SIGTERM, _on_term)

    # Ready marker for process supervisors (cluster_utils / CLI).
    print(json.dumps({
        "node_id": daemon.node_id,
        "dispatch_port": daemon.dispatch_port,
        "object_port": daemon.transfer.port,
        "session_dir": daemon.session_dir,
    }), flush=True)
    daemon.run_forever()


if __name__ == "__main__":
    main()
