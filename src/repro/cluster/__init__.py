"""Cluster-scale serving and co-design: many tuned dies behind one
front-end.

  * ``ClusterSpec`` / ``homogeneous`` — the budget-validated die inventory;
  * ``ClusterRouter`` / ``SimClock`` — health-aware, least-loaded
    precision/accuracy/deadline admission routing with degrade-don't-drop
    cross-die migration (``docs/cluster.md``);
  * ``TraceConfig`` / ``RequestClass`` / ``generate`` / ``replay`` /
    ``latency_stats`` — the seeded bursty/diurnal open-loop load generator;
  * ``ChipClass`` / ``tune_cluster`` — chip-mix + fleet-size co-design
    under total area/TDP budgets.

Telemetry: pass ``tracer=repro.telemetry.Tracer()`` to ``ClusterRouter``
(shared by every die, so span trees survive cross-die migration) and to
``replay`` (arrival system events); ``trace_cluster`` below wires both and
returns the tracer (``docs/telemetry.md``).
"""
from repro.cluster.loadgen import (Arrival, RequestClass, TraceConfig,
                                   generate, latency_stats, replay)
from repro.cluster.router import ClusterRouter, SimClock
from repro.cluster.spec import ClusterSpec, homogeneous
from repro.cluster.tune import ChipClass, ClusterTuneResult, tune_cluster
from repro.telemetry import Tracer


def trace_cluster(router: ClusterRouter) -> Tracer:
    """Attach a fresh recording ``Tracer`` to an already-built router (and
    every die replica it owns); returns the tracer.  Pass it as
    ``replay(..., tracer=...)`` to record arrivals too."""
    tracer = Tracer()
    router.tracer = tracer
    for name, srv in router.servers.items():
        srv.tracer = tracer
        srv.trace_site = name
    return tracer


__all__ = [
    "Arrival", "ChipClass", "ClusterRouter", "ClusterSpec",
    "ClusterTuneResult", "RequestClass", "SimClock", "TraceConfig",
    "Tracer", "generate", "homogeneous", "latency_stats", "replay",
    "trace_cluster", "tune_cluster",
]
