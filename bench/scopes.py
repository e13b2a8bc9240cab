"""Device time by scope and by pipeline tick: a profiler trace of the window
read together with the engine's wave records.

The trace names a device op by its HLO instruction (``fusion.162``) and
carries no metadata; the wave record's ``program`` (``runtime.engine.
Program``) maps each instruction of the program that wave ran to its
innermost device scope (``program.op_scopes()``: ``layer.mlp``,
``transport.ring_shift``, ...; ``repro.obs.trace.SCOPES``). A chip's ops
belong to the program of the ``prefill_wave seq<S> b<B>`` host span they
run in (the span ends with ``block_until_ready``, so a wave's device work
lies inside it).

Every read returns None where the waves carry no program (a system built
before the scopes existed) or the trace holds no such span.
"""
from __future__ import annotations

import bisect
import collections
import re
import sys
import traceback

import tracefile

RING = "transport.ring_shift"
ATTN = ("layer.attn_self", "layer.attn_pool")
KERNELS = ("chunk_attention", "pool_attention_paged", "pool_attention")
UNSCOPED = "unscoped"
OUTSIDE = "outside_waves"
_WAVE = re.compile(r"prefill_wave seq(\d+) b(\d+)$")


def device_id(plane: str):
    """``/device:TPU:3`` -> 3; None for a plane name without an id."""
    m = re.search(r"(\d+)$", plane)
    return int(m.group(1)) if m else None


def _scope_map(program):
    """``program.op_scopes()``, or None with the traceback on stderr where
    the program cannot give it: a per-layer reading is left out then, and
    the traced run goes on."""
    try:
        return program.op_scopes()
    except Exception:
        print("[scopes] op_scopes() failed; scope metrics left out:\n"
              + traceback.format_exc(), file=sys.stderr)
        return None


def wave_spans(run):
    """[(start, end, wave record)] of the window's ``prefill_wave`` host
    spans (trace clock, ns), each with the wave record of its sequence
    length and batch size; None without a trace or without programs."""
    if run.trace is None:
        return None
    programs = {}
    for w in run.waves:
        if not hasattr(w.get("program"), "op_scopes"):
            return None
        programs[(w["seq"], len(w["rids"]))] = w
    out = []
    for name, s, e in run.trace.host:
        m = _WAVE.match(name)
        if m is None or e <= run.trace.w0 or s >= run.trace.w1:
            continue
        w = programs.get((int(m.group(1)), int(m.group(2))))
        if w is None:
            return None
        out.append((s, e, w))
    return sorted(out, key=lambda x: x[0]) or None


def op_table(run):
    """{plane: [(op, start, end, self ns, scope, wave index)]}: every op of
    the window with its self time (``tracefile.self_times``), its scope
    (``UNSCOPED`` where the program gives none, ``OUTSIDE`` for an op that
    starts in no wave span) and the index of its wave span in
    ``wave_spans``; None where ``wave_spans`` is None. Kept on ``run`` for
    the other readers of the same run."""
    if "_scopes_op_table" not in vars(run):
        run._scopes_op_table = _op_table(run)
    return run._scopes_op_table


def _op_table(run):
    spans = wave_spans(run)
    if spans is None:
        return None
    starts = [s for s, _, _ in spans]
    programs = {id(w["program"]): w["program"] for _, _, w in spans}
    maps = {k: _scope_map(p) for k, p in programs.items()}
    if any(m is None for m in maps.values()):
        return None
    scopes = {id(w): maps[id(w["program"])] for _, _, w in spans}
    table = {}
    for plane in run.trace.devices:
        rows = []
        for name, s, e, own in tracefile.self_times(run.trace.ops(plane)):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= spans[i][1]:
                rows.append((name, s, e, own, OUTSIDE, None))
                continue
            scope = scopes[id(spans[i][2])].get(name, UNSCOPED)
            rows.append((name, s, e, own, scope, i))
        table[plane] = rows
    return table


def scope_seconds(run):
    """{plane: {scope: device self seconds}}, the ``UNSCOPED`` and
    ``OUTSIDE`` shares included; None without scopes."""
    table = op_table(run)
    if table is None:
        return None
    out = {}
    for plane, rows in table.items():
        acc = collections.Counter()
        for _, _, _, own, scope, _ in rows:
            acc[scope] += own * 1e-9
        out[plane] = dict(acc)
    return out


def exposed_collective_by_scope(run):
    """{plane: {scope: seconds}}: per scope, the time its collectives run
    and no other op's own work does (``Trace.exposed_collective_s``, split
    by the scope of the collective); None without scopes."""
    table = op_table(run)
    if table is None:
        return None
    out = {}
    for plane, rows in table.items():
        leaves = tracefile.union(
            (s, e) for n, s, e, own, _, _ in rows
            if own == e - s and not tracefile.COLLECTIVE.search(n))
        by_scope = collections.defaultdict(list)
        for n, s, e, _, scope, _ in rows:
            if tracefile.COLLECTIVE.search(n):
                by_scope[scope].append((s, e))
        out[plane] = {
            scope: tracefile.total(tracefile.subtract(
                tracefile.union(iv), leaves)) * 1e-9
            for scope, iv in by_scope.items()}
    return out


def tick_compute(run):
    """[(wave record, {plane: (stage, [c_0 .. c_{T-1}])})] for every wave of
    the window: ``c_t`` is the chip's non-collective self seconds in tick
    ``t``, the interval that ends where the tick's ``transport.ring_shift``
    ends (its ``-done`` op where the collective is asynchronous) and starts
    where the previous one ended. None where a chip shows another count of
    ring shifts than the wave's ``num_ticks``, or without scopes."""
    table = op_table(run)
    if table is None:
        return None
    spans = wave_spans(run)
    out = []
    by_wave = {plane: collections.defaultdict(list) for plane in table}
    for plane, rows in table.items():
        for r in rows:
            by_wave[plane][r[5]].append(r)
    for i, (_, _, w) in enumerate(spans):
        program = w["program"]
        stage_of = {d: s for s, devs in enumerate(program.stage_devices)
                    for d in devs}
        per_chip = {}
        for plane in table:
            mine = by_wave[plane][i]
            ring = [r for r in mine if r[4] == RING]
            done = [r for r in ring
                    if tracefile.base_name(r[0]).endswith("-done")]
            ends = sorted(r[2] for r in (done or ring))
            if len(ends) != w["num_ticks"]:
                return None
            c = [0.0] * len(ends)
            for name, s, _, own, _, _ in mine:
                t = bisect.bisect_right(ends, s)
                if t < len(ends) and not tracefile.COLLECTIVE.search(name):
                    c[t] += own * 1e-9
            stage = stage_of.get(device_id(plane))
            if stage is None:
                return None
            per_chip[plane] = (stage, c)
        out.append((w, per_chip))
    return out


def bubble_frac(run):
    """Share of all tick compute spent on ticks whose chunk index ``t -
    stage`` lies outside ``[0, M)`` (fill and drain), over all chips and
    waves."""
    ticks = tick_compute(run)
    if not ticks:
        return None
    masked = total = 0.0
    for w, per_chip in ticks:
        m = w["program"].num_chunks
        for stage, c in per_chip.values():
            for t, ct in enumerate(c):
                total += ct
                if not 0 <= t - stage < m:
                    masked += ct
    return masked / total if total > 0 else None


def tick_imbalance_frac(run):
    """sum_t (max_s c[s,t] - mean_s c[s,t]) / sum_t max_s c[s,t] over every
    tick of every wave: the share of the slowest chip's tick compute that
    the other chips wait through."""
    ticks = tick_compute(run)
    if not ticks:
        return None
    spread = top = 0.0
    for _, per_chip in ticks:
        rows = [c for _, c in per_chip.values()]
        for col in zip(*rows):
            spread += max(col) - sum(col) / len(col)
            top += max(col)
    return spread / top if top > 0 else None


def attn_glue_frac(run):
    """Device self time in the attention scopes outside the two kernels'
    custom calls, over all device self time in those scopes."""
    table = op_table(run)
    if table is None:
        return None
    glue = total = 0.0
    for rows in table.values():
        for name, _, _, own, scope, _ in rows:
            if scope in ATTN:
                total += own
                if tracefile.base_name(name) not in KERNELS:
                    glue += own
    return glue / total if total > 0 else None
