"""Continuous-batching serving tier.

The traffic side of the millions-of-users path: an open-loop request
queue, an iteration-level :class:`BatchScheduler` (finished sequences
exit and new requests join between decode steps — no drain-the-batch
barrier), and a *planner-informed* :class:`AdmissionController` that
consults the bound ExecutionPlan's batch-dependent scheme crossovers
and phase budgets before growing the decode batch, staging the next
batch bucket's plan through ``PlanBinder`` ahead of admission so batch
growth is a pointer flip (mirroring the failover swap).

Dataflow: queue -> admit -> schedule -> bind (see ARCHITECTURE.md).
Everything here is numpy-only; with a ``PlannerProbe`` and no engine
the scheduler runs on a virtual clock, so the whole tier is
simulation-testable on CPU like SimProbe.  Plugging in a ``ServeEngine``
makes the same scheduler drive real prefill/decode.
"""

from repro.serving.admission import (AdmissionController, AdmissionDecision,
                                     PlannerProbe)
from repro.serving.queue import (DEADLINE_CLASSES, Request, RequestQueue)
from repro.serving.scheduler import BatchScheduler
from repro.serving.traffic import TrafficConfig, TrafficGenerator

__all__ = [
    "AdmissionController", "AdmissionDecision", "BatchScheduler",
    "DEADLINE_CLASSES", "PlannerProbe", "Request", "RequestQueue",
    "TrafficConfig", "TrafficGenerator",
]
