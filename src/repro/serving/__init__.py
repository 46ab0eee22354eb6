"""Concurrent what-if serving (the ROADMAP's async-serving milestone).

A :class:`~repro.serving.service.DesignCalculatorService` is a long-lived
scoring service: it holds the device-resident parameter banks of its
registered hardware profiles plus the packed-frontier/segment caches, and
answers concurrent what-if (design / hardware / workload), workload-sweep
and auto-completion questions by coalescing a window of them into one
fused scoring call per hardware profile (see ``docs/serving.md``).

Production traffic hardening (PR 6): requests are admitted through
bounded priority lanes (interactive vs bulk) with optional per-session
cost budgets, carry deadlines, and shed explicitly under overload
(:mod:`repro.serving.admission`, :mod:`repro.serving.lanes`); the
service warm-restarts from an on-disk snapshot of the synthesis memos.

Multi-device routing (PR 7): a scoring-shard pool
(:mod:`repro.serving.shards`) partitions each coalesced window's spliced
frontier/sweep across local devices, dispatches the partitions
concurrently with deadlines probed between shard dispatches, and merges
bit-identical totals before any future resolves.

Self-healing (PR 8): failed/timed-out shard parts retry on a different
device behind a per-device circuit breaker; non-finite fused results
fall back pooled -> fused-flat -> grouped oracle per evaluation
(answers carry the producing ``engine`` tag); a supervisor resurrects a
crashed worker, failing in-flight futures with the typed
:class:`~repro.serving.admission.WorkerCrashed`.  Fault-tolerance state
is observable via ``Service.health()`` and the ``stats()`` counters, and
exercisable deterministically with :mod:`repro.testing.faults`.
"""
from repro.serving.admission import (BudgetExceeded, DeadlineExceeded,
                                     RejectedError, ServiceError,
                                     ServiceStoppedError, SessionBudgets,
                                     TokenBucket, WorkerCrashed,
                                     request_cost)
from repro.serving.lanes import BULK, INTERACTIVE, LaneScheduler
from repro.serving.service import (DesignCalculatorService, ServiceSession,
                                   ServiceStats)
from repro.serving.shards import (NonFiniteScore, ScoringShardPool,
                                  ShardTimeout)

__all__ = [
    "DesignCalculatorService", "ServiceSession", "ServiceStats",
    "ServiceError", "RejectedError", "BudgetExceeded", "DeadlineExceeded",
    "ServiceStoppedError", "WorkerCrashed", "TokenBucket", "SessionBudgets",
    "request_cost", "LaneScheduler", "INTERACTIVE", "BULK",
    "ScoringShardPool", "ShardTimeout", "NonFiniteScore",
]
