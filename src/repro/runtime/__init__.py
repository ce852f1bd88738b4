"""Runtime: fault tolerance and the chaos-hardening layer (deterministic
fault injection + self-healing long-run driving).

Light-on-import by design: :mod:`repro.runtime.chaos` and
:mod:`repro.runtime.fault` are stdlib-only (they are imported by leaf
modules like the checkpoint writer and the kernel dispatchers);
:mod:`repro.runtime.resilient` pulls in jax + the solver stack and is
imported explicitly by its consumers.
"""

from repro.runtime.chaos import (
    BackendError,
    Fault,
    FaultPlan,
    InjectedCrash,
    InjectedFault,
    InjectedIOError,
    TransientError,
    WorkerDeath,
    injected,
)
from repro.runtime.fault import (
    Heartbeat,
    HeartbeatStatus,
    SupervisorReport,
    read_heartbeat,
    supervise,
)

__all__ = [
    "BackendError",
    "Fault",
    "FaultPlan",
    "Heartbeat",
    "HeartbeatStatus",
    "InjectedCrash",
    "InjectedFault",
    "InjectedIOError",
    "SupervisorReport",
    "TransientError",
    "WorkerDeath",
    "injected",
    "read_heartbeat",
    "supervise",
]
