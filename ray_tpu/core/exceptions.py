"""Exception hierarchy.

Capability-equivalent to the reference's exception surface
(reference: python/ray/exceptions.py): user-code failures wrapped with the
remote traceback, actor death, object loss, cancellation, and get timeout.
"""

from __future__ import annotations

import traceback
from typing import Optional


class RayTpuError(Exception):
    """Base class for all framework errors."""


class TaskError(RayTpuError):
    """A remote task raised an exception.

    Carries the remote traceback text; re-raised at every `get` on any of
    the task's return refs, and propagated through dependent tasks
    (error poisoning, as in the reference).
    """

    def __init__(self, function_name: str, cause: BaseException,
                 remote_tb: Optional[str] = None):
        self.function_name = function_name
        self.cause = cause
        self.remote_tb = remote_tb or "".join(
            traceback.format_exception(type(cause), cause, cause.__traceback__)
        )
        super().__init__(
            f"Task {function_name!r} failed: "
            f"{type(cause).__name__}: {cause}\n"
            f"--- remote traceback ---\n{self.remote_tb}"
        )

    def __reduce__(self):
        try:
            import pickle
            pickle.dumps(self.cause)
            cause = self.cause
        except Exception:  # noqa: BLE001 — unpicklable user exception
            cause = RuntimeError(f"{type(self.cause).__name__}: {self.cause}")
        return (TaskError, (self.function_name, cause, self.remote_tb))


class ActorError(RayTpuError):
    """Base for actor-related failures."""


class ActorDiedError(ActorError):
    def __init__(self, actor_id_hex: str, reason: str = ""):
        self.actor_id_hex = actor_id_hex
        super().__init__(f"Actor {actor_id_hex} died. {reason}".strip())


class ActorUnavailableError(ActorError):
    pass


class ObjectLostError(RayTpuError):
    def __init__(self, object_id_hex: str):
        self.object_id_hex = object_id_hex
        super().__init__(
            f"Object {object_id_hex} was lost and could not be reconstructed."
        )


class TaskCancelledError(RayTpuError):
    pass


class GetTimeoutError(RayTpuError, TimeoutError):
    pass


class RuntimeEnvSetupError(RayTpuError):
    pass


class OutOfMemoryError(RayTpuError):
    pass


class ObjectStoreFullError(RayTpuError):
    pass


class PendingCallsLimitExceeded(RayTpuError):
    pass
