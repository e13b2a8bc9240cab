"""Merged Chrome/Perfetto trace recorder (stdlib-only).

One recorder, one file, three event families (ISSUE 6 tentpole #2):

- scheduler task intervals (``task``/``mark``, the scheduler's surface —
  pid = stage, tid = request),
- engine wave / per-tick stage spans (``span`` — arbitrary pid/tid),
- counter tracks (``counter`` — ``"ph": "C"`` events Perfetto renders as
  stacked area charts: KV occupancy and wire bytes per stage).

Timestamps are SECONDS on whatever clock the caller uses (the scheduler's
virtual clock or ``time.perf_counter`` deltas); export converts to the
trace-event microsecond unit. ``export`` writes atomically
(``_io.atomic_write_text``) so an interrupted run never leaves a truncated
JSON artifact.

Inside the served path (``runtime.engine``) three more pieces live here:

- ``SpanLog``: host spans of the engine (``engine.step``, ``engine.admit``,
  ``engine.prepare``, ``engine.dispatch``, ``engine.compile``,
  ``engine.device_wait``, ``engine.fetch``, ``prefill_wave ...``), each a
  ``jax.profiler.TraceAnnotation`` under its bare name (so it lands on the
  device trace's clock) and a ``(name, start, end, ids)`` entry in a
  bounded in-memory record on the ``perf_counter`` clock;
- ``process_events()``: one process-wide listener for JAX's tracing,
  backend-compile and persistent-cache-hit events and for Python GC pauses
  longer than ``GC_PAUSE_S`` (``host.gc`` spans); executors read it as
  deltas;
- ``hlo_op_scopes``: the device scopes (``SCOPES``, set with
  ``jax.named_scope`` in ``core``) of a compiled program's HLO
  instructions, read from the ``op_name`` metadata of its text
  (``fresh_compiled_text``: a compile that no cache answers).
"""
from __future__ import annotations

import collections
import gc
import json
import re
import time
from dataclasses import asdict, dataclass, field
from typing import Any, Deque, Dict, List, Mapping, Optional, Tuple

from repro.obs._io import atomic_write_text

# the device scopes of the served path, outermost first where they nest
# (a transport or kernel call inside a layer scope is named by the inner)
SCOPES = ("stage.embed", "stage.head", "layer.attn_proj", "layer.attn_self",
          "layer.attn_pool", "layer.kv_write", "layer.mlp",
          "layer.ssm_proj", "layer.ssd", "layer.moe_route",
          "layer.moe_experts", "layer.moe_combine", "layer.moe_shared",
          "transport.ring_shift", "transport.pair_shift",
          "transport.stage_psum", "transport.tp_psum",
          "transport.tp_reduce_scatter", "transport.tp_all_gather")
GC_PAUSE_S = 1e-3          # GC pauses recorded as host.gc spans
SPAN_LOG_MAX = 1 << 16     # entries kept per SpanLog


@dataclass(frozen=True)
class TaskEvent:
    rid: int
    chunk: int
    stage: int
    start: float          # seconds (scheduler clock)
    finish: float


@dataclass(frozen=True)
class MarkEvent:
    rid: int
    kind: str             # arrival | admit | finish | reject
    time: float


@dataclass(frozen=True)
class SpanEvent:
    name: str
    pid: Any              # process row (stage index or a string label)
    tid: Any              # thread row within the process
    start: float
    finish: float
    cat: str = "span"
    args: Optional[Dict[str, Any]] = None


@dataclass(frozen=True)
class CounterEvent:
    name: str             # counter track name (one track per (pid, name))
    pid: Any
    time: float
    values: Dict[str, float] = field(default_factory=dict)


class TraceRecorder:
    """Accumulates scheduler/engine/telemetry events; no-op when disabled."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.tasks: List[TaskEvent] = []
        self.marks: List[MarkEvent] = []
        self.spans: List[SpanEvent] = []
        self.counters: List[CounterEvent] = []
        self._pid_names: Dict[Any, str] = {}

    def task(self, rid: int, chunk: int, stage: int,
             start: float, finish: float) -> None:
        if self.enabled:
            self.tasks.append(TaskEvent(rid, chunk, stage, start, finish))

    def mark(self, rid: int, kind: str, time: float) -> None:
        if self.enabled:
            self.marks.append(MarkEvent(rid, kind, time))

    def span(self, name: str, *, pid: Any, tid: Any, start: float,
             finish: float, cat: str = "span",
             args: Optional[Dict[str, Any]] = None) -> None:
        """Record a complete-duration ("ph": "X") interval."""
        if self.enabled:
            self.spans.append(SpanEvent(name, pid, tid, start, finish,
                                        cat, args))

    def counter(self, name: str, *, pid: Any, time: float,
                values: Mapping[str, float]) -> None:
        """Record one sample on a counter track ("ph": "C")."""
        if self.enabled:
            self.counters.append(CounterEvent(name, pid, time,
                                              dict(values)))

    def process_name(self, pid: Any, name: str) -> None:
        """Label a process row (overrides the default ``stage {pid}``)."""
        if self.enabled:
            self._pid_names[pid] = name

    # -------------------------------------------------------------- merging
    def absorb(self, other: "TraceRecorder", *, pid_prefix: str = "") -> None:
        """Fold another recorder's events into this one under a per-source
        process namespace — the multi-cell fleet timeline (``repro.fleet``):
        ONE file where every cell keeps its own process rows
        (``cell0/stage 3``, ``cell1/engine``, ...). All of ``other``'s pids
        (task stages, span/counter pids, registered process names) are
        re-keyed to ``f"{pid_prefix}{pid}"``; task intervals become chunk
        spans (tid = request) and lifecycle marks become zero-duration
        request instants, so absorbed cells never collide with this
        recorder's own integer stage pids. With an empty prefix events copy
        through verbatim."""
        if not self.enabled:
            return

        def _pid(p: Any) -> Any:
            if not pid_prefix:
                return p
            base = f"stage {p}" if isinstance(p, int) else str(p)
            return f"{pid_prefix}{base}"

        if not pid_prefix:
            self.tasks.extend(other.tasks)
            self.marks.extend(other.marks)
        else:
            for t in other.tasks:
                self.span(f"r{t.rid}/c{t.chunk}", pid=_pid(t.stage),
                          tid=t.rid, start=t.start, finish=t.finish,
                          cat="chunk", args={"rid": t.rid, "chunk": t.chunk,
                                             "stage": t.stage})
            for m in other.marks:
                self.span(f"{m.kind} r{m.rid}", pid=f"{pid_prefix}requests",
                          tid=m.rid, start=m.time, finish=m.time,
                          cat="request")
        for s in other.spans:
            self.spans.append(SpanEvent(s.name, _pid(s.pid), s.tid, s.start,
                                        s.finish, s.cat, s.args))
        for c in other.counters:
            self.counters.append(CounterEvent(c.name, _pid(c.pid), c.time,
                                              dict(c.values)))
        for p, name in other._pid_names.items():
            self._pid_names[_pid(p)] = (f"{pid_prefix}{name}" if pid_prefix
                                        else name)

    # ------------------------------------------------------------- export
    def events(self) -> Dict[str, List[Dict[str, Any]]]:
        """Raw event dicts for offline analysis."""
        return {"tasks": [asdict(t) for t in self.tasks],
                "marks": [asdict(m) for m in self.marks],
                "spans": [asdict(s) for s in self.spans],
                "counters": [asdict(c) for c in self.counters]}

    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON: pid = stage, tid = request, ts in us."""
        ev: List[Dict[str, Any]] = []
        for t in self.tasks:
            ev.append({
                "name": f"r{t.rid}/c{t.chunk}",
                "cat": "chunk",
                "ph": "X",
                "ts": t.start * 1e6,
                "dur": (t.finish - t.start) * 1e6,
                "pid": t.stage,
                "tid": t.rid,
                "args": {"rid": t.rid, "chunk": t.chunk, "stage": t.stage},
            })
        for m in self.marks:
            ev.append({
                "name": m.kind,
                "cat": "request",
                "ph": "i",
                "s": "g",
                "ts": m.time * 1e6,
                "pid": 0,
                "tid": m.rid,
            })
        for s in self.spans:
            rec = {
                "name": s.name,
                "cat": s.cat,
                "ph": "X",
                "ts": s.start * 1e6,
                "dur": (s.finish - s.start) * 1e6,
                "pid": s.pid,
                "tid": s.tid,
            }
            if s.args:
                rec["args"] = s.args
            ev.append(rec)
        for c in self.counters:
            ev.append({
                "name": c.name,
                "cat": "counter",
                "ph": "C",
                "ts": c.time * 1e6,
                "pid": c.pid,
                "tid": 0,
                "args": c.values,
            })
        pids = ({t.stage for t in self.tasks} | {s.pid for s in self.spans}
                | {c.pid for c in self.counters} | set(self._pid_names))
        for p in sorted(pids, key=str):
            name = self._pid_names.get(
                p, f"stage {p}" if isinstance(p, int) else str(p))
            ev.append({"name": "process_name", "ph": "M", "pid": p,
                       "args": {"name": name}})
        return {"traceEvents": ev, "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        """Atomically write the Chrome trace JSON to ``path``."""
        return atomic_write_text(path, json.dumps(self.chrome_trace()))


# ------------------------------------------------------- engine host spans

class _Span:
    """One open span of a ``SpanLog``: a profiler annotation under the bare
    name plus ``perf_counter`` start/end."""

    __slots__ = ("log", "name", "ids", "ann", "start", "end")

    def __init__(self, log: "SpanLog", name: str, ids: Dict[str, Any]):
        self.log, self.name, self.ids = log, name, ids

    def __enter__(self) -> "_Span":
        from jax.profiler import TraceAnnotation
        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self.ann.__exit__(*exc)
        self.log.spans.append((self.name, self.start, self.end, self.ids))

    @property
    def dur(self) -> float:
        return self.end - self.start


class SpanLog:
    """Bounded record of host spans ``(name, start, end, ids)``, times in
    ``perf_counter`` seconds; ``ids`` holds the wave index (``wave``) that
    ties a span to its wave record and, through it, to request ids."""

    def __init__(self, maxlen: int = SPAN_LOG_MAX):
        self.spans: Deque[Tuple[str, float, float, Dict[str, Any]]] = \
            collections.deque(maxlen=maxlen)

    def span(self, name: str, **ids) -> _Span:
        """``with log.span("engine.fetch", wave=3) as s: ...``; ``s.dur``
        after the block."""
        return _Span(self, name, ids)

    def add(self, name: str, start: float, end: float, **ids) -> None:
        """Record a span measured elsewhere (no profiler annotation)."""
        self.spans.append((name, start, end, ids))


# --------------------------------------------- process-wide event listener

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class ProcessEvents:
    """Counts of JAX tracing, backend compiles (or cache-backed compile
    requests) and persistent-cache loads, and the GC pauses longer than
    ``GC_PAUSE_S``, since the process started listening. Readers take
    deltas (``counts()`` before and after; ``gc_pauses(since)``)."""

    def __init__(self):
        self._counts = {"traces": 0, "compiles": 0, "cache_loads": 0}
        self._gc: Deque[Tuple[float, float, int]] = collections.deque(
            maxlen=4096)
        self._gc_t0 = 0.0

    def counts(self) -> Dict[str, int]:
        return dict(self._counts)

    def gc_pauses(self, since: float) -> List[Tuple[float, float, int]]:
        """``(start, end, generation)`` of the pauses that ended after
        ``since`` (``perf_counter`` seconds)."""
        return [p for p in self._gc if p[1] > since]

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == _TRACE_EVENT:
            self._counts["traces"] += 1
        elif event == _COMPILE_EVENT:
            self._counts["compiles"] += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == _CACHE_HIT_EVENT:
            self._counts["cache_loads"] += 1

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._gc_t0 = now
        elif now - self._gc_t0 > GC_PAUSE_S:
            self._gc.append((self._gc_t0, now, int(info["generation"])))


_PROCESS_EVENTS: Optional[ProcessEvents] = None


def process_events() -> ProcessEvents:
    """The process's one listener, registered on first use (executors are
    built many times per process; listeners are never added twice)."""
    global _PROCESS_EVENTS
    if _PROCESS_EVENTS is None:
        import jax
        pe = ProcessEvents()
        jax.monitoring.register_event_duration_secs_listener(pe._on_duration)
        jax.monitoring.register_event_listener(pe._on_event)
        gc.callbacks.append(pe._on_gc)
        _PROCESS_EVENTS = pe
    return _PROCESS_EVENTS


# ------------------------------------------------------ device op scopes

_HLO_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?'
                        r'metadata=\{[^}]*?op_name="([^"]*)"')


def fresh_compiled_text(lowered) -> str:
    """The compiled text of a ``jax.stages.Lowered``, compiled afresh.

    The persistent compile cache keys a program without its debug info, so
    an entry written by the same program under other scope names returns
    their ``op_name`` metadata; and ``Lowered.compile()`` hands back the
    executable the jitted call already holds. A compiler option forces a
    new compile (``xla_detailed_logging`` only changes XLA's logging), and
    the persistent cache is off while it runs."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    cc.reset_cache()
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return lowered.compile({"xla_detailed_logging": True}).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def hlo_op_scopes(hlo_text: str, scopes=SCOPES) -> Dict[str, str]:
    """``{HLO instruction name: innermost scope of ``scopes``}`` for every
    instruction of a compiled program's text whose ``op_name`` path passes
    through one of ``scopes``. Instruction names are the names a profiler
    trace gives the device ops."""
    wanted = set(scopes)
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTR.match(line)
        if m is None:
            continue
        for part in reversed(m.group(2).split("/")):
            if part in wanted:
                out[m.group(1)] = part
                break
    return out
