"""Unified telemetry layer: metrics registry, span tracing, serving timeline.

The observability subsystem the stats surfaces are rewired onto
(DESIGN.md §9): ``Engine``/``BatchEngine`` (host dispatch times of first
tokens and decode steps, queue wait, chunk counts, admit/complete/starvation
events, spans of each step), ``SlabArena``/``ExtentPool``
(grow events, copied bytes, utilization), ``TwoPhasePipeline`` (freeze/thaw
latency, elements frozen), ``CapacityPlanner``/``TenantPlanner`` (host
contacts, via ``gauge_fn`` callbacks).  The legacy ``EngineStats``/
``BatchStats``/``FreezeStats`` dataclasses survive as thin read-only views
over these registries.

Hard contract: recording a metric or a span is host-side Python only —
**zero device→host transfers on the append/decode hot path**.  Device
scalars go through ``Counter.add_lazy`` and materialize only at explicit
drain points (``snapshot()`` / metric reads), enforced by the transfer-guard
test in ``tests/serving/test_telemetry.py``.
"""
from repro.obs import device
from repro.obs.device import DeviceCounterPlane
from repro.obs.flightrec import FlightRecorder
from repro.obs.registry import (
    Counter,
    Gauge,
    GaugeFn,
    Histogram,
    MetricsRegistry,
    default_registry,
)
from repro.obs.timeline import ServingTimeline
from repro.obs.trace import Span, Tracer

__all__ = [
    "Counter",
    "DeviceCounterPlane",
    "FlightRecorder",
    "Gauge",
    "GaugeFn",
    "Histogram",
    "MetricsRegistry",
    "ServingTimeline",
    "Span",
    "Tracer",
    "default_registry",
    "device",
]
