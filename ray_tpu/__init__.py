"""ray_tpu — a TPU-native distributed computing framework.

Public API surface mirrors the reference's (reference:
python/ray/__init__.py — init/shutdown, @remote, get/put/wait/cancel/kill,
actors, placement groups, runtime context), re-designed TPU-first: the
scheduler is ICI-topology-aware, collectives are XLA collectives over
meshes (`ray_tpu.parallel`), and the AI libraries (`data`, `train`,
`serve`, `tune`, `rl`) run SPMD programs on TPU slices.
"""

from __future__ import annotations

import inspect as _inspect
from typing import Any, Dict, List, Optional, Sequence, Union

from ._version import __version__
from .core import runtime as _runtime
from .core.actor import (ActorClass, ActorHandle, exit_actor,
                         get_actor, method)
from .core.exceptions import (
    ActorDiedError,
    ActorError,
    GetTimeoutError,
    ObjectLostError,
    RayTpuError,
    TaskCancelledError,
    TaskError,
)
from .core.graphable import graphable, is_graphable
from .core.object_ref import ObjectRef
from .core.placement_group import (
    PlacementGroup,
    placement_group,
    remove_placement_group,
)
from .core.remote_function import RemoteFunction
from .core.runtime import ObjectRefGenerator, RuntimeContext
from .core.task import (
    NodeAffinitySchedulingStrategy,
    PlacementGroupSchedulingStrategy,
    SliceAffinitySchedulingStrategy,
    SpreadSchedulingStrategy,
)

__all__ = [
    "__version__", "init", "shutdown", "is_initialized", "remote", "get",
    "put", "wait", "cancel", "kill", "get_actor", "exit_actor", "method",
    "graphable", "is_graphable",
    "ObjectRef",
    "ObjectRefGenerator", "ActorClass", "ActorHandle", "RemoteFunction",
    "PlacementGroup", "placement_group", "remove_placement_group",
    "get_runtime_context", "cluster_resources", "available_resources",
    "timeline", "nodes", "method",
    "NodeAffinitySchedulingStrategy", "PlacementGroupSchedulingStrategy",
    "SliceAffinitySchedulingStrategy", "SpreadSchedulingStrategy",
    "RayTpuError", "TaskError", "ActorError", "ActorDiedError",
    "ObjectLostError", "TaskCancelledError", "GetTimeoutError",
]


# ---------------------------------------------------------------------------
# Lifecycle
# ---------------------------------------------------------------------------

def init(address: Optional[str] = None, *,
         num_cpus: Optional[float] = None, num_tpus: Optional[float] = None,
         resources: Optional[Dict[str, float]] = None,
         num_worker_procs: int = 0,
         namespace: Optional[str] = None,
         _system_config: Optional[Dict[str, Any]] = None,
         ignore_reinit_error: bool = True, **_compat) -> None:
    """Start (or connect to) the runtime.

    address="tpu://host:port" enters CLIENT MODE: this process becomes a
    remote driver against a ClientServer-hosted runtime (reference:
    ray.init("ray://...") → python/ray/util/client/). All other options
    start a local runtime.

    num_worker_procs > 0 adds an out-of-process execution plane: that
    many spawned worker processes (true parallelism, crash isolation)
    sharing the zero-copy shm object store (core/worker_proc.py).

    Reference parity: ray.init (python/ray/_private/worker.py:1227).
    """
    if address is not None and (address.startswith("tpu://")
                                or address.startswith("ray://")):
        from . import client as _client_mod

        if _client_mod.get_client() is not None:
            if ignore_reinit_error:
                return
            raise RuntimeError("already connected in client mode")
        _client_mod.connect(address, namespace=namespace)
        return
    if _runtime.is_initialized():
        if ignore_reinit_error:
            return
        raise RuntimeError("ray_tpu is already initialized")
    # A bare "host:port" address joins a daemon-backed cluster as a
    # driver (reference: ray.init(address="host:port") joining a
    # `ray start` cluster); node daemons appear as schedulable nodes.
    _runtime.init_runtime(
        num_cpus=num_cpus, num_tpus=num_tpus, resources=resources,
        num_worker_procs=num_worker_procs,
        cluster_address=address,
        advertise_host=_compat.get("advertise_host", "127.0.0.1"),
        _system_config=_system_config)
    if namespace:
        _runtime.global_runtime().namespace = namespace


def shutdown() -> None:
    from . import client as _client_mod

    _client_mod.disconnect()
    _runtime.shutdown_runtime()


def is_initialized() -> bool:
    from . import client as _client_mod

    return (_runtime.is_initialized()
            or _client_mod.get_client() is not None)


def _client():
    """Active client context, or None (client-mode routing hook)."""
    from . import client as _client_mod

    return _client_mod.get_client()


# ---------------------------------------------------------------------------
# @remote
# ---------------------------------------------------------------------------

def remote(*args, **options):
    """Decorate a function → RemoteFunction, or a class → ActorClass.

    Supports both ``@remote`` and ``@remote(num_tpus=1, ...)`` forms
    (reference: python/ray/__init__.py remote / worker.py make_decorator).
    """
    if len(args) == 1 and not options and (
            callable(args[0]) or _inspect.isclass(args[0])):
        return _make_remote(args[0], {})
    if args:
        raise TypeError("@remote takes keyword options only")

    def decorator(obj):
        return _make_remote(obj, options)

    return decorator


def _make_remote(obj, options):
    if _inspect.isclass(obj):
        return ActorClass(obj, options)
    if callable(obj):
        return RemoteFunction(obj, options)
    raise TypeError(f"@remote target must be function or class: {obj!r}")


# @method lives in core.actor (imported above): per-method defaults for
# num_returns / concurrency_group, consumed at submit time.


# ---------------------------------------------------------------------------
# Object API
# ---------------------------------------------------------------------------

def put(value: Any) -> ObjectRef:
    c = _client()
    if c is not None:
        return c.put(value)
    return _runtime.global_runtime().put(value)


def get(refs: Union[ObjectRef, Sequence[ObjectRef]],
        *, timeout: Optional[float] = None):
    c = _client()
    if c is not None:
        from .client.common import ClientObjectRef

        if isinstance(refs, ClientObjectRef):
            return c.get(refs, timeout)
        return c.get(list(refs), timeout)
    rt = _runtime.global_runtime()
    if isinstance(refs, ObjectRef):
        return rt.get([refs], timeout)[0]
    if isinstance(refs, ObjectRefGenerator):
        raise TypeError(
            "Pass individual refs from the generator to get(), not the "
            "generator itself.")
    if not isinstance(refs, (list, tuple)):
        raise TypeError(f"get() expects ObjectRef or list, got {type(refs)}")
    for r in refs:
        if not isinstance(r, ObjectRef):
            raise TypeError(f"get() list items must be ObjectRef: {type(r)}")
    return rt.get(list(refs), timeout)


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None, fetch_local: bool = True):
    if isinstance(refs, ObjectRef):
        raise TypeError("wait() expects a list of ObjectRefs")
    if num_returns > len(refs):
        raise ValueError(
            f"num_returns={num_returns} exceeds {len(refs)} provided refs")
    c = _client()
    if c is not None:
        return c.wait(list(refs), num_returns, timeout)
    return _runtime.global_runtime().wait(
        list(refs), num_returns, timeout, fetch_local)


def cancel(ref: ObjectRef, *, force: bool = False,
           recursive: bool = True) -> None:
    c = _client()
    if c is not None:
        c.cancel(ref, force=force)
        return
    _runtime.global_runtime().cancel(ref, force=force)


def kill(actor: ActorHandle, *, no_restart: bool = True) -> None:
    c = _client()
    if c is not None:
        from .client.client import ClientActorHandle

        if isinstance(actor, ClientActorHandle):
            c.kill_actor(actor._actor_id, no_restart=no_restart)
            return
    _runtime.global_runtime().kill_actor(
        actor._actor_id, no_restart=no_restart)


# ---------------------------------------------------------------------------
# Introspection
# ---------------------------------------------------------------------------

def get_runtime_context() -> RuntimeContext:
    return RuntimeContext()


def cluster_resources() -> Dict[str, float]:
    c = _client()
    if c is not None:
        return c.cluster_resources()
    return _runtime.global_runtime().cluster_resources()


def available_resources() -> Dict[str, float]:
    c = _client()
    if c is not None:
        return c.available_resources()
    return _runtime.global_runtime().available_resources()


def nodes() -> List[dict]:
    rt = _runtime.global_runtime()
    return [
        {
            "NodeID": n.node_id, "Alive": n.alive,
            "Resources": n.total.to_dict(), "Labels": dict(n.labels),
        }
        for n in rt.scheduler.nodes()
    ]


def timeline(filename: Optional[str] = None):
    """Chrome-trace dump (reference: ray timeline CLI)."""
    events = _runtime.global_runtime().timeline()
    if filename:
        import json
        with open(filename, "w") as f:
            json.dump(events, f)
        return None
    return events
