"""The program's spans in a traced window: self time, counters, and the
device's idle time put down to the innermost span.

``harness.trace.reduce`` reads the harness's own spans (``bench.*``).
The program writes spans of its layers too (``repro.*``, made by
``repro.obs.span``), with its counters as the events' stats.  This
reduction reads both kinds on the host planes:

* a span's time is its part inside ``bench.window``, as in
  ``trace.reduce``; its self time is that part less what its children
  (the spans nested in it on the same thread) cover;
* a span's counters are summed over its events that overlap the window;
* each idle gap of a device goes to the innermost span (the shortest)
  that covers the gap's midpoint, found exactly by one sweep over every
  span, however deep the nesting and however many children a span has
  (``idle_by_span``); and a gap's length is also split by what it
  overlaps, each instant to the innermost span then (``idle_self_s``),
  since one long gap can outlast several host phases.

On a trace without ``repro.*`` spans the window, busy time, span times
and counts, operation times and idle attribution equal
``trace.reduce``'s wherever its look-back of the last 64 spans finds the
owner (``test_spans.py`` pins both on a recorded chip trace).

``READINGS`` computes the per-layer quantities the program's spans give,
per control window or per request.  ``bench/phases.py`` traces a cell's
window and prints this reduction.
"""
from __future__ import annotations

import heapq
import warnings
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from . import trace

PREFIXES = (trace.SPAN_PREFIX, "repro.")
NO_SPAN = "bench.window (no span)"

#: (start_ns, end_ns, name, thread, counters)
Span = Tuple[float, float, str, int, Dict[str, float]]


@dataclass
class Spans:
    """Host spans and device idle time of one traced window (seconds)."""

    window_s: float = 0.0
    busy_s: float = 0.0
    n_devices: int = 0
    op_s: Dict[str, float] = field(default_factory=dict)
    span_s: Dict[str, float] = field(default_factory=dict)
    span_n: Dict[str, int] = field(default_factory=dict)
    self_s: Dict[str, float] = field(default_factory=dict)
    counters: Dict[str, Dict[str, float]] = field(default_factory=dict)
    idle_by_span: Dict[str, float] = field(default_factory=dict)
    idle_gaps_n: Dict[str, int] = field(default_factory=dict)
    idle_self_s: Dict[str, float] = field(default_factory=dict)

    def breakdown(self) -> List[list]:
        """``[span, idle s in its self time, self s, idle s of the gaps
        whose midpoint it holds, those gaps, n]`` for every span, most
        idle first."""
        names = set(self.span_s) | set(self.idle_by_span) | set(
            self.idle_self_s)
        rows = [[n, self.idle_self_s.get(n, 0.0), self.self_s.get(n, 0.0),
                 self.idle_by_span.get(n, 0.0), self.idle_gaps_n.get(n, 0),
                 self.span_n.get(n, 0)] for n in names]
        return sorted(rows, key=lambda r: (-r[1], -r[3], -r[2], r[0]))


def _host_spans(profile) -> List[Span]:
    out: List[Span] = []
    thread = 0
    with warnings.catch_warnings():
        # jaxlib's stats type warns that it has no __module__; a warning
        # turned into an error inside the binding aborts the process
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in profile.planes:
            if plane.name.startswith("/device:"):
                continue
            for ln in plane.lines:
                thread += 1
                for ev in ln.events:
                    if ev.name.startswith(PREFIXES):
                        out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                    ev.name, thread,
                                    {k: v for k, v in ev.stats
                                     if isinstance(v, (int, float))}))
    return out


def _self_times(spans: List[Span], w0: float, w1: float,
                red: Spans) -> None:
    """Self time of each span: its part of the window less its children's,
    found with a stack per thread."""
    clip = lambda s, e: max(0.0, min(e, w1) - max(s, w0))
    own: Dict[int, float] = {}
    stacks: Dict[int, List[Tuple[float, int]]] = {}
    for i, (s, e, n, th, _) in sorted(enumerate(spans),
                                      key=lambda kv: (kv[1][3], kv[1][0],
                                                      -kv[1][1])):
        stack = stacks.setdefault(th, [])
        while stack and stack[-1][0] <= s:
            stack.pop()
        own[i] = clip(s, e)
        if stack and e <= stack[-1][0]:
            own[stack[-1][1]] -= own[i]
        stack.append((e, i))
    for i, (_, _, n, _, _) in enumerate(spans):
        red.self_s[n] = red.self_s.get(n, 0.0) + own[i] * 1e-9


def _owners(spans: List[Span], times: List[float]) -> List[str]:
    """For each of the ascending ``times``, the shortest span covering it
    (ties: the earliest), by one sweep: spans that started are kept in a
    heap by length, and one that ended before a time ends before every
    later one too."""
    order = sorted(spans, key=lambda sp: (sp[0], sp[1], sp[2]))
    heap: List[Tuple[float, float, float, str]] = []
    out, k = [], 0
    for t in times:
        while k < len(order) and order[k][0] <= t:
            s, e, n = order[k][:3]
            heapq.heappush(heap, (e - s, s, e, n))
            k += 1
        while heap and heap[0][2] < t:
            heapq.heappop(heap)
        out.append(heap[0][3] if heap else NO_SPAN)
    return out


def _segments(spans: List[Span], w0: float,
              w1: float) -> Tuple[List[float], List[str]]:
    """The window cut at every span boundary, and the innermost span of
    each piece: ``bounds[i]``..``bounds[i + 1]`` belongs to
    ``owners[i]``."""
    bounds = sorted({w0, w1} | {min(max(x, w0), w1)
                                for sp in spans for x in sp[:2]})
    order = sorted(spans, key=lambda sp: (sp[0], sp[1], sp[2]))
    heap: List[Tuple[float, float, float, str]] = []
    owners, k = [], 0
    for t in bounds[:-1]:
        while k < len(order) and order[k][0] <= t:
            s, e, n = order[k][:3]
            heapq.heappush(heap, (e - s, s, e, n))
            k += 1
        while heap and heap[0][2] <= t:
            heapq.heappop(heap)
        owners.append(heap[0][3] if heap else NO_SPAN)
    return bounds, owners


def _split(gaps: List[Tuple[float, float]], bounds: List[float],
           owners: List[str], into: Dict[str, float]) -> None:
    """Add each gap's overlap with each piece to the piece's owner."""
    i = 0
    for g0, g1 in gaps:
        while bounds[i + 1] <= g0:
            i += 1
        j = i
        while j < len(owners) and bounds[j] < g1:
            lap = min(g1, bounds[j + 1]) - max(g0, bounds[j])
            if lap > 0:
                into[owners[j]] = into.get(owners[j], 0.0) + lap * 1e-9
            j += 1


def reduce(profile) -> Spans:
    """Reduce a ProfileData to the spans of its ``bench.window``."""
    spans = _host_spans(profile)
    red = Spans()
    windows = [(s, e) for s, e, n, _, _ in spans if n == trace.WINDOW]
    if not windows:
        return red
    w0, w1 = windows[0]
    red.window_s = (w1 - w0) * 1e-9
    inner = sorted((sp for sp in spans
                    if sp[2] != trace.WINDOW and sp[1] > w0 and sp[0] < w1),
                   key=lambda sp: (sp[0], sp[1], sp[2]))
    for s, e, n, _, counts in inner:
        red.span_s[n] = red.span_s.get(n, 0.0) + (min(e, w1) - max(s, w0)) * 1e-9
        red.span_n[n] = red.span_n.get(n, 0) + 1
        agg = red.counters.setdefault(n, {})
        for k, v in counts.items():
            agg[k] = agg.get(k, 0) + v
    _self_times(inner, w0, w1, red)
    bounds, owners = _segments(inner, w0, w1)
    busy_total = 0.0
    for plane in profile.planes:
        if not trace._is_device_plane(plane.name):
            continue
        lines = {ln.name: [(ev.start_ns, ev.start_ns + ev.duration_ns,
                            trace._op_name(ev.name)) for ev in ln.events]
                 for ln in plane.lines}
        if trace.OPS_LINE not in lines:
            lines[trace.OPS_LINE] = [ev for name, evs in lines.items()
                                     if name not in trace.NOT_OPS
                                     for ev in evs]
        ops = [(s, e, n) for s, e, n in lines[trace.OPS_LINE]
               if e > w0 and s < w1]
        if not ops:
            continue
        red.n_devices += 1
        for s, e, n in ops:
            red.op_s[n] = red.op_s.get(n, 0.0) + (min(e, w1) - max(s, w0)) * 1e-9
        busy = trace._union(trace._clip([(s, e) for s, e, _ in ops], w0, w1))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2])
                if g1 > g0]
        names = _owners(inner, [0.5 * (g0 + g1) for g0, g1 in gaps])
        for (g0, g1), name in zip(gaps, names):
            red.idle_by_span[name] = (red.idle_by_span.get(name, 0.0)
                                      + (g1 - g0) * 1e-9)
            red.idle_gaps_n[name] = red.idle_gaps_n.get(name, 0) + 1
        _split(gaps, bounds, owners, red.idle_self_s)
    if red.n_devices:
        red.busy_s = busy_total / red.n_devices
        for d in (red.op_s, red.idle_by_span, red.idle_self_s):
            for k in d:
                d[k] /= red.n_devices
    return red


# --- per-layer readings of the program's spans ---------------------------

def _per(x: Optional[float], n: int) -> Optional[float]:
    return None if x is None or not n else x / n


def _span_ms(name: str) -> Callable[[Spans, int], Optional[float]]:
    def read(sp: Spans, n: int) -> Optional[float]:
        s = sp.span_s.get(name, 0.0)
        return _per(1e3 * s if s > 0 else None, n)
    return read


def _csr_pad_share(sp: Spans, n: int) -> Optional[float]:
    c = sp.counters.get("repro.fleet.pack_csr", {})
    if not c.get("slots"):
        return None
    return 100.0 * (1.0 - c.get("packets", 0) / c["slots"])


def _device_calls(sp: Spans, n: int) -> Optional[float]:
    return _per(sp.span_n.get("repro.query.launch") or None, n)


def _h2d_bytes(sp: Spans, n: int) -> Optional[float]:
    c = sp.counters.get("repro.query.launch")
    return None if c is None else _per(c.get("h2d_bytes", 0), n)


#: metric -> (reading of a Spans, what it is per: "windows" or "requests").
#: A reading is None where its span is not in the trace.
READINGS: Dict[str, Tuple[Callable[[Spans, int], Optional[float]], str]] = {
    "replay.csr_pack_ms_per_window": (_span_ms("repro.fleet.pack_csr"),
                                      "windows"),
    "replay.sync_wait_ms_per_window": (_span_ms("repro.fleet.sync"),
                                       "windows"),
    "replay.csr_pad_share": (_csr_pad_share, "windows"),
    "query.host_prep_ms_per_request": (_span_ms("repro.query.prep"),
                                       "requests"),
    "query.device_calls_per_request": (_device_calls, "requests"),
    "query.h2d_bytes_per_request": (_h2d_bytes, "requests"),
}


def readings(sp: Spans, *, windows: int = 0,
             requests: int = 0) -> Dict[str, float]:
    """Every reading the trace holds, per control window or request."""
    per = {"windows": windows, "requests": requests}
    out = {}
    for name, (read, unit) in READINGS.items():
        v = read(sp, per[unit])
        if v is not None:
            out[name] = v
    return out

