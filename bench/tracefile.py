"""Reduction of a profiler trace (``.xplane.pb``) to device times.

Reads the trace with ``jax.profiler.ProfileData``: every ``/device:*``
plane's ``XLA Ops`` line gives the operations that ran on that chip, and
the host planes give the benchmark's own annotations (``bench_window``
marks the measured window; ``bench_step``, ``bench_wait`` and the engine's
``prefill_wave ...`` say what the host was doing). All times are clipped to
the window.

    python3 bench/tracefile.py <file.xplane.pb>     # print what the trace holds
"""
from __future__ import annotations

import collections
import gzip
import re
import sys

WINDOW = "bench_window"
HOST_SPANS = ("bench_window", "bench_step", "bench_wait", "bench_submit",
              "prefill_wave")
COLLECTIVE = re.compile(r"collective-permute|all-reduce|all-gather|"
                        r"reduce-scatter|all-to-all|\bsend\b|\brecv\b")
OP_LINE = "XLA Ops"


class TraceError(ValueError):
    pass


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(events, w0, w1):
    """Events (name, start, end) cut to the window [w0, w1)."""
    return [(n, max(s, w0), min(e, w1)) for n, s, e in events
            if e > w0 and s < w1]


def short_name(event_name: str) -> str:
    """The HLO instruction's name from a trace event's name, which is the
    whole instruction (``%fusion.162 = bf16[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def base_name(name: str) -> str:
    """``fusion.162`` -> ``fusion``; ``chunk_attention.11`` ->
    ``chunk_attention``."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def self_times(events):
    """[(name, start, end, self)] for events (name, start, end) of one
    line, where ``self`` is the duration minus that of the events nested
    directly inside (a ``while`` or ``conditional`` holds the operations
    of its body); an event with nothing inside is a leaf, ``self`` equal
    to its duration."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [e[2] - e[1] for e in evs]
    stack = []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(n, s, e, o) for (n, s, e), o in zip(evs, own)]


class Trace:
    """Device operations per chip and host spans of one trace, in
    nanoseconds on the trace's clock. Operations are named by their HLO
    instruction (``short_name``)."""

    def __init__(self, devices: dict, host: list):
        self.devices = devices      # plane name -> [(op, start, end)]
        self.host = host            # [(span, start, end)]
        spans = [(s, e) for n, s, e in host if n == WINDOW]
        if not spans:
            raise TraceError(f"no {WINDOW!r} span in the trace")
        self.w0, self.w1 = spans[0]
        if not devices:
            raise TraceError("no device plane with an 'XLA Ops' line")
        self._self = {p: self_times(self.ops(p)) for p in devices}

    @classmethod
    def load(cls, path: str) -> "Trace":
        """Read an ``.xplane.pb`` file, or one compressed with gzip
        (``.gz``)."""
        from jax.profiler import ProfileData
        with open(path, "rb") as f:
            data = f.read()
        if path.endswith(".gz"):
            data = gzip.decompress(data)
        pd = ProfileData.from_serialized_xspace(data)
        devices, host = {}, []
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name == OP_LINE:
                        devices[plane.name] = [
                            (short_name(e.name), e.start_ns, e.end_ns)
                            for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(HOST_SPANS):
                            host.append((e.name, e.start_ns, e.end_ns))
        return cls(devices, host)

    # ------------------------------------------------------------- reads
    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9

    def ops(self, plane: str):
        return clip(self.devices[plane], self.w0, self.w1)

    def busy_s(self) -> dict:
        """Seconds in which some operation ran, per chip."""
        return {p: total(union((s, e) for _, s, e in self.ops(p))) * 1e-9
                for p in self.devices}

    def op_seconds(self, match) -> float:
        """Summed self time of the operations whose base name
        ``match``es, over all chips."""
        return sum(o for p in self.devices for n, _, _, o in self._self[p]
                   if match(base_name(n))) * 1e-9

    def exposed_collective_s(self) -> dict:
        """Per chip: seconds in which a collective ran and no other
        operation's own work did (operations that hold others, such as a
        ``while``, count only where nothing inside them runs)."""
        out = {}
        for p in self.devices:
            coll = union((s, e) for n, s, e in self.ops(p)
                         if COLLECTIVE.search(n))
            leaves = [(s, e) for n, s, e, o in self._self[p]
                      if o == e - s and not COLLECTIVE.search(n)]
            out[p] = total(subtract(coll, union(leaves))) * 1e-9
        return out

    def top_ops(self, n: int = 10):
        """[[op, seconds per chip], ...] of the ``n`` operations with the
        most self time."""
        acc = collections.Counter()
        for p in self.devices:
            for name, _, _, o in self._self[p]:
                acc[name] += o * 1e-9
        k = len(self.devices)
        return [[name, t / k] for name, t in acc.most_common(n)]

    def idle_gaps(self, n: int = 10):
        """[[host activity, seconds], ...]: the ``n`` longest stretches of
        the window in which the busiest chip ran nothing, each named by
        the innermost host span around its middle."""
        busy_s = self.busy_s()
        plane = max(busy_s, key=busy_s.get)
        busy = union((s, e) for _, s, e in self.ops(plane))
        gaps = subtract([[self.w0, self.w1]], busy)
        spans = [h for h in self.host if h[0] != WINDOW]
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = (s + e) / 2
            around = [h for h in spans if h[1] <= mid < h[2]]
            label = (min(around, key=lambda h: h[2] - h[1])[0].split()[0]
                     if around else "host_other")
            out.append([label, (e - s) * 1e-9])
        return out


def describe(path: str) -> None:
    """Print planes, lines, event counts and the most frequent names."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            dur = collections.Counter()
            for e in evs:
                dur[e.name] += e.duration_ns
            print(f"  line {line.name!r}: {len(evs)} events")
            for name, t in dur.most_common(12):
                print(f"    {t / 1e6:12.3f} ms  x{names[name]:<6d} "
                      f"{short_name(name)[:100]}")


if __name__ == "__main__":
    describe(sys.argv[1])
