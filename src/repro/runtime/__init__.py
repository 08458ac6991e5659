"""Distributed-runtime substrate: checkpointing, failure handling, stragglers.

``CheckpointManager`` lives in :mod:`repro.runtime.checkpoint` and is not
re-exported here: it needs JAX, and the store's host processes import
:mod:`repro.runtime.failure` without ever touching a device (one process
per chip).
"""

from repro.runtime.failure import FailureInjector, Heartbeat, SimulatedFailure
from repro.runtime.straggler import StepTimeMonitor

__all__ = [
    "FailureInjector",
    "Heartbeat",
    "SimulatedFailure",
    "StepTimeMonitor",
]
