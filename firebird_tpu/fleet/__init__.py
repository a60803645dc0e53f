"""Crash-tolerant fleet scheduling: a lease/heartbeat/fence job queue
(fleet.queue), the worker loop that drains it (fleet.worker), the
multi-tile plan builder (fleet.plan), and the elastic control plane
that sizes the fleet from queue pressure (fleet.policy +
fleet.supervisor).  docs/ROBUSTNESS.md "Fleet scheduling" / "Elastic
operation" are the operator stories; tools/fleet_chaos.py and
tools/elastic_soak.py are the proofs."""

from firebird_tpu.fleet.queue import (FencedStore, FleetQueue, Lease,
                                      LeaseLost, StaleFence, queue_path)
from firebird_tpu.fleet.worker import WEDGED_EXIT, FleetWorker, make_queue
from firebird_tpu.fleet.plan import enqueue_repairs, enqueue_tile_plan
from firebird_tpu.fleet.policy import Decision, QueueSnapshot, ScalePolicy
from firebird_tpu.fleet.supervisor import Supervisor, workers_hold_accelerator

__all__ = [
    "FencedStore", "FleetQueue", "Lease", "LeaseLost", "StaleFence",
    "queue_path", "WEDGED_EXIT", "FleetWorker", "make_queue",
    "enqueue_repairs",
    "enqueue_tile_plan", "Decision", "QueueSnapshot", "ScalePolicy",
    "Supervisor",
    "workers_hold_accelerator",
]
