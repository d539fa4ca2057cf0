"""Fleet tier: N serving replicas behind one placement-aware router.

One paged :class:`~consensusml_tpu.serve.engine.Engine` is fast; N of
them behind a router is the product (ROADMAP item 1). This package
spends the control signals the serving observability plane already
exports:

- :mod:`~consensusml_tpu.fleet.replicas` — replica lifecycle: spawn
  (in-process for tests, subprocess for deployment), readiness
  gate on warmup, kill detection + restart under a supervisor.
- :mod:`~consensusml_tpu.fleet.router` — a threaded line-JSON TCP
  front-end that proxies streams to replicas, choosing placement from a
  per-replica score over scraped signals (``/healthz`` readiness, KV
  headroom ``consensusml_pool_hbm_free_bytes``, queue depth) with
  (tenant, prompt-prefix-hash) affinity; failures re-dispatch to the
  next-best replica as continuations, so an accepted stream is never
  lost.
- :mod:`~consensusml_tpu.fleet.controller` — an alert consumer driving
  drain/spawn decisions off the burn-rate rules, plus canary
  generation rollout: bump ONE replica, soak, then promote fleet-wide
  or roll back.

See docs/fleet.md for placement scoring, re-dispatch semantics, and the
canary state machine; ``tools/fleetctl.py`` is the CLI entry point.
"""

from consensusml_tpu.fleet.controller import CanaryState, FleetController
from consensusml_tpu.fleet.replicas import (
    ExternalReplica,
    InProcessReplica,
    ReplicaSet,
    SubprocessReplica,
)
from consensusml_tpu.fleet.router import FleetRouter

__all__ = [
    "CanaryState",
    "ExternalReplica",
    "FleetController",
    "FleetRouter",
    "InProcessReplica",
    "ReplicaSet",
    "SubprocessReplica",
]
