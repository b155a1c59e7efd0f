"""TpuTrainer — SPMD training orchestration over worker actors.

Capability-equivalent to the reference's Train stack
(reference: python/ray/train/base_trainer.py:74 BaseTrainer.fit :579,
data_parallel_trainer.py:26 DataParallelTrainer,
_internal/backend_executor.py:65 BackendExecutor — worker-group creation
in a placement group, rendezvous, run train_loop_per_worker, stream
`report()` results back, FailureConfig-driven group restarts), redesigned
TPU-first: no NCCL process-group bootstrapping — each worker drives its
chips through a jax Mesh built from the ScalingConfig's ParallelPlan, and
gang placement uses STRICT_PACK (or SliceAffinity) so all workers land on
one ICI slice.
"""

from __future__ import annotations

import logging
import threading
import traceback
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from .. import remote
from ..core.placement_group import (
    placement_group,
    remove_placement_group,
)
from ..core.task import PlacementGroupSchedulingStrategy
from .checkpoint import Checkpoint, CheckpointManager
from .config import CheckpointConfig, FailureConfig, RunConfig, ScalingConfig
from .session import ReportItem, _set_session, _TrainSession

logger = logging.getLogger("ray_tpu.train")


@dataclass
class Result:
    metrics: Dict[str, Any] = field(default_factory=dict)
    checkpoint: Optional[Checkpoint] = None
    error: Optional[BaseException] = None
    path: str = ""
    metrics_history: List[Dict[str, Any]] = field(default_factory=list)

    @property
    def best_checkpoints(self):
        return [self.checkpoint] if self.checkpoint else []


class _TrainWorker:
    """Worker actor: hosts one SPMD rank's session and runs the user loop.
    Streamed method `run` yields ReportItems as training progresses
    (reference: backend_executor start_training + TrainingIterator
    polling, trainer.py:31 — here a streaming generator replaces the
    polling)."""

    def __init__(self, rank: int, world_size: int, name: str, plan_bytes):
        import cloudpickle

        self.rank = rank
        self.world_size = world_size
        self.name = name
        self.plan = cloudpickle.loads(plan_bytes) if plan_bytes else None

    def run(self, fn_bytes: bytes, loop_config: Optional[Dict[str, Any]],
            dataset_shards: Optional[Dict[str, Any]],
            start_checkpoint=None, rendezvous: Optional[Dict[str, Any]]
            = None):
        import cloudpickle

        fn = cloudpickle.loads(fn_bytes)
        session = _TrainSession(
            self.rank, self.world_size, self.name, loop_config,
            dataset_shards, self.plan, start_checkpoint=start_checkpoint)

        def _target():
            _set_session(session)
            joined = False
            try:
                if rendezvous is not None:
                    self._join_gang(rendezvous)
                    joined = True
                from .._private import compile_cache

                compile_cache.enable()
                import inspect

                if loop_config is not None and len(
                        inspect.signature(fn).parameters) >= 1:
                    fn(loop_config)
                else:
                    fn()
                if joined:
                    # Clean finish only: after a failure peers may be
                    # stuck in a collective and shutdown would block;
                    # the dedicated worker process dies with the actor.
                    from ..parallel.multihost import shutdown_multihost

                    shutdown_multihost()
            except BaseException as e:  # noqa: BLE001
                session.error = e
            finally:
                _set_session(None)
                session.finished.set()
                session.queue.put(None)

        t = threading.Thread(target=_target, daemon=True,
                             name=f"train-loop-{self.rank}")
        t.start()
        while True:
            item = session.queue.get()
            if item is None:
                break
            yield item
        if session.error is not None:
            raise session.error
        yield ReportItem({"__final__": True}, None, self.rank)

    def _join_gang(self, rdv: Dict[str, Any]) -> None:
        """jax.distributed rendezvous for this rank (reference:
        backend_executor.py:124 start → worker group → rendezvous →
        train; torch/config.py:62 TCP store ↔ here the coordinator
        address rides the control plane's KV)."""
        from ..parallel.multihost import init_multihost

        from ..parallel import multihost as mh

        if mh._initialized:
            # jax.distributed.initialize is once-per-process: a second
            # rank landing in this process would silently skip init and
            # hang the whole gang at the coordinator. Surface it.
            raise RuntimeError(
                "multihost rank cannot share a process with another "
                "rank (jax.distributed already initialized here); "
                "ensure each worker gets its own OS process — daemon "
                "placement or ray_tpu.init(num_worker_procs=...)")
        client = None
        if rdv.get("control_address"):
            from .._native.control_client import ControlClient

            host, _, port = rdv["control_address"].partition(":")
            client = ControlClient(int(port), host=host)
        try:
            init_multihost(
                coordinator_address=rdv.get("coordinator_address"),
                num_processes=self.world_size,
                process_id=self.rank,
                control_client=client,
                kv_key=rdv["kv_key"],
                port=rdv["coordinator_port"])
        finally:
            if client is not None:
                client.close()


class TpuTrainer:
    """reference-parity surface: TpuTrainer(train_loop_per_worker,
    train_loop_config=..., scaling_config=..., run_config=...,
    datasets=...).fit() -> Result."""

    def __init__(self, train_loop_per_worker: Callable,
                 *, train_loop_config: Optional[Dict[str, Any]] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None):
        self.train_loop = train_loop_per_worker
        self.train_loop_config = train_loop_config
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}
        # Checkpoint every worker's session starts from; train loops read
        # it via train.get_checkpoint() (reference:
        # base_trainer.py resume_from_checkpoint → session checkpoint).
        # Tune's PBT exploit and trial restore set this between fits.
        self.resume_from_checkpoint = resume_from_checkpoint
        # Subclass hook (TorchTrainer): rank -> SchedulingStrategy,
        # replacing the default placement-group gang placement.
        self._strategy_factory: Optional[Callable[[int], Any]] = None

    # ------------------------------------------------------------------
    def fit(self) -> Result:
        failures_allowed = self.run_config.failure_config.max_failures
        attempt = 0
        storage = self.run_config.resolve_storage()
        cc = self.run_config.checkpoint_config
        manager = CheckpointManager(
            storage, cc.num_to_keep, cc.checkpoint_score_attribute,
            cc.checkpoint_score_order)
        # Retries resume from the newest checkpoint WITHIN this fit;
        # the caller's resume_from_checkpoint is restored afterwards so
        # a reused trainer instance (Tuner trials) starts fresh.
        orig_resume = self.resume_from_checkpoint
        try:
            while True:
                try:
                    return self._fit_once(manager)
                except (KeyboardInterrupt, SystemExit):
                    raise  # user interrupts are not trial failures
                except Exception as e:  # noqa: BLE001
                    attempt += 1
                    if failures_allowed >= 0 \
                            and attempt > failures_allowed:
                        return Result(error=e, path=storage)
                    # Restarted groups resume from the newest checkpoint
                    # the failed attempt registered (reference:
                    # FailureConfig recovery restores the latest
                    # reported checkpoint).
                    latest = manager.latest()
                    if latest is not None:
                        self.resume_from_checkpoint = latest
                    logger.warning(
                        "Training attempt %d failed (%s); restarting "
                        "worker group (%d restarts left).", attempt,
                        type(e).__name__, failures_allowed - attempt)
        finally:
            self.resume_from_checkpoint = orig_resume

    def _make_rendezvous(self, n: int) -> Dict[str, Any]:
        """Per-attempt rendezvous spec: a fresh coordinator port and a
        fresh KV key, so a retried gang can never join a crashed gang's
        coordinator (reference: backend_executor re-creates the TCP
        store on restart)."""
        import uuid

        from ..core.runtime import global_runtime

        rt = global_runtime()
        rdv: Dict[str, Any] = {
            "coordinator_port": None,
            "kv_key": f"multihost/{self.run_config.name or 'train'}/"
                      f"{uuid.uuid4().hex[:12]}",
            "control_address": None,
            "coordinator_address": None,
        }
        if rt.remote_plane is not None:
            # Cluster mode: rank 0 picks a port free on ITS host and
            # publishes the coordinator address in the control plane's
            # KV; peers poll it (SURVEY §3.3 — the rendezvous path the
            # whole stack exists to serve).
            rdv["control_address"] = rt.remote_plane.address
        else:
            # Single-machine worker processes share the driver's host,
            # so a driver-side port probe is authoritative here.
            port = _free_port()
            rdv["coordinator_port"] = port
            rdv["coordinator_address"] = f"127.0.0.1:{port}"
        return rdv

    def _fit_once(self, manager: CheckpointManager) -> Result:
        import cloudpickle

        sc = self.scaling_config
        n = sc.num_workers
        storage = self.run_config.resolve_storage()

        # Gang placement: one bundle per worker (reference:
        # BackendExecutor start creates the PG; TPU-native default is
        # PACK onto one slice).
        from .. import get as ray_get, kill as ray_kill

        if sc.multihost and n > 1 and self._strategy_factory is None:
            rt = None
            from ..core.runtime import global_runtime

            rt = global_runtime()
            if rt.remote_plane is None:
                # Local mode: each rank MUST be its own OS process —
                # jax.distributed.initialize is once-per-process, so
                # thread actors sharing the driver process cannot form
                # a gang. Route ranks to dedicated worker processes
                # (same plane the torch/TF trainers use).
                if (rt.worker_pool is None
                        or rt.worker_pool.num_workers < n):
                    have = (0 if rt.worker_pool is None
                            else rt.worker_pool.num_workers)
                    raise RuntimeError(
                        f"ScalingConfig(multihost=True) outside a "
                        f"daemon cluster needs {n} worker processes "
                        f"but the runtime has {have}: call "
                        f"ray_tpu.init(num_worker_procs={n}) or "
                        "connect to a cluster "
                        "(ray_tpu.init(address=...))")
                from ..core.task import NodeAffinitySchedulingStrategy

                self._strategy_factory = lambda rank: \
                    NodeAffinitySchedulingStrategy(node_id="node-procs",
                                                   soft=False)

        pg = None
        if self._strategy_factory is None:
            pg = placement_group(
                [sc.worker_resources() for _ in range(n)],
                strategy=sc.placement_strategy)
        workers: List[Any] = []
        history: List[Dict[str, Any]] = []
        last_ckpt: Optional[Checkpoint] = None
        error: Optional[BaseException] = None
        try:
            if pg is not None:
                pg.wait(timeout=None)

            WorkerActor = remote(num_cpus=0)(_TrainWorker)
            plan_bytes = cloudpickle.dumps(sc.plan) if sc.plan else None
            for rank in range(n):
                if self._strategy_factory is not None:
                    strategy = self._strategy_factory(rank)
                else:
                    strategy = PlacementGroupSchedulingStrategy(
                        placement_group=pg,
                        placement_group_bundle_index=rank)
                workers.append(
                    WorkerActor.options(
                        scheduling_strategy=strategy,
                        num_cpus=sc.cpus_per_worker,
                        num_tpus=sc.tpus_per_worker or None,
                        resources=sc.resources_per_worker or None,
                    ).remote(rank, n, self.run_config.name or "train",
                             plan_bytes))

            # Shard datasets across workers (streaming_split if possible).
            shards_per_worker: List[Dict[str, Any]] = [
                dict() for _ in range(n)]
            for name, ds in self.datasets.items():
                if hasattr(ds, "streaming_split"):
                    split = ds.streaming_split(n, equal=True)
                    for r in range(n):
                        shards_per_worker[r][name] = split[r]
                else:
                    for r in range(n):
                        shards_per_worker[r][name] = ds

            fn_bytes = cloudpickle.dumps(self.train_loop)
            rendezvous = None
            if sc.multihost and n > 1:
                rendezvous = self._make_rendezvous(n)
            streams = [
                w.run.options(num_returns="streaming").remote(
                    fn_bytes, self.train_loop_config, shards_per_worker[r],
                    self.resume_from_checkpoint, rendezvous)
                for r, w in enumerate(workers)
            ]

            # Drain all workers' report streams; rank-0 metrics drive
            # results, rank-0 checkpoints are persisted.
            def drain(stream, rank):
                nonlocal last_ckpt, error
                try:
                    for ref in stream:
                        item: ReportItem = ray_get(ref)
                        if item.metrics.get("__final__"):
                            continue
                        if item.checkpoint is not None and rank == 0:
                            stored = manager.register(
                                item.checkpoint, item.metrics)
                            if stored is not None:
                                last_ckpt = stored
                        if rank == 0:
                            history.append(item.metrics)
                except BaseException as e:  # noqa: BLE001
                    if error is None:
                        error = e

            threads = [
                threading.Thread(target=drain, args=(s, r), daemon=True)
                for r, s in enumerate(streams)
            ]
            for t in threads:
                t.start()
            # Abort the attempt on the FIRST rank failure: surviving
            # ranks may be blocked in a collective/rendezvous with the
            # dead peer and their streams stay silent for minutes — the
            # group teardown below unblocks them (reference:
            # backend_executor shuts the whole worker group down when
            # any worker fails).
            while True:
                alive = [t for t in threads if t.is_alive()]
                if not alive or error is not None:
                    break
                alive[0].join(timeout=0.2)
        finally:
            for w in workers:
                try:
                    ray_kill(w)
                except Exception:  # noqa: BLE001
                    pass
            if pg is not None:
                remove_placement_group(pg)

        if error is not None:
            raise error
        return Result(
            metrics=history[-1] if history else {},
            checkpoint=last_ckpt or manager.latest(),
            path=storage,
            metrics_history=history,
        )


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ProcessPlaneTrainerMixin:
    """Shared scaffolding for trainers whose ranks each need their own
    OS process (torch gloo process groups, TF collective servers).
    Rank actors run as DEDICATED worker processes (worker_proc.py
    spawn_dedicated) that die with the actor — every fit attempt gets
    fresh processes, which is what lets frameworks with no in-process
    teardown (TF) re-rendezvous on retries."""

    def _init_process_plane(self) -> None:
        from ..core.task import NodeAffinitySchedulingStrategy

        self._strategy_factory = lambda rank: \
            NodeAffinitySchedulingStrategy(node_id="node-procs",
                                           soft=False)

    def _require_worker_procs(self, what: str) -> "None":
        from ..core.runtime import global_runtime

        rt = global_runtime()
        n = self.scaling_config.num_workers
        if rt.worker_pool is None or rt.worker_pool.num_workers < n:
            have = 0 if rt.worker_pool is None \
                else rt.worker_pool.num_workers
            raise RuntimeError(
                f"{what} needs {n} worker processes but the runtime "
                f"has {have}; call ray_tpu.init(num_worker_procs={n})")
