"""Continuous chunk-level scheduling: cross-request pipelining subsystem.

``ChunkScheduler`` keeps the chunked pipeline bubble-free across request
boundaries; ``KVLeaseManager`` guards the MBKR slot budget under concurrent
in-flight requests; ``SchedMetrics``/``TraceRecorder`` provide TTFT/SLO
accounting and Chrome-format JSON traces.
"""
from repro.sched.kvlease import (KVLeaseManager, Lease, LeaseEvent,
                                 request_lease_events, slot_budget_bytes)
from repro.sched.metrics import RequestRecord, SchedMetrics, fleet_summary
from repro.sched.scheduler import (POLICIES, ChunkPlan, ChunkScheduler,
                                   SchedRequest, poisson_arrivals)
from repro.obs.trace import TraceRecorder
