"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device metrics.

The reduction reads what the TPU runtime records on each device plane
(``/device:TPU:<n>``): one event per executed XLA program on the
``XLA Modules`` line, named ``<jit name>(<fingerprint>)``.  Host spans come
from the ``/host:CPU`` plane, where ``jax.profiler.TraceAnnotation`` writes
the harness's own span names.  Device and host events share one clock in
the trace.

What it computes, over the harness's ``window`` span:

* busy seconds per device: the union of the program intervals;
* device seconds per program name (the stable kernel names the per-layer
  readers look up, e.g. ``jit_senseamp_resolve_trials``);
* the idle time between busy intervals, split by the innermost harness
  span open on the host at each moment.
"""
from __future__ import annotations

import glob
import os
import re
from collections import Counter
from dataclasses import dataclass, field

#: the harness span that brackets the measured window
WINDOW_SPAN = "window"
#: label of an idle gap that no harness span covers
UNLABELLED = "outside_spans"

_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclass
class TraceSummary:
    window_s: float
    #: busy seconds, averaged over the devices that ran a program
    busy_s: float
    #: device seconds per program name, summed over devices
    program_s: dict[str, float] = field(default_factory=dict)
    #: program executions per name
    program_calls: dict[str, int] = field(default_factory=dict)
    #: idle seconds per innermost host span, averaged over devices
    idle_by_span: dict[str, float] = field(default_factory=dict)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.program_s.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def program_name(event_name: str) -> str:
    """``jit_nary_bitwise(123456)`` -> ``jit_nary_bitwise``."""
    return _FINGERPRINT.sub("", event_name)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: list[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    """Complement of merged ``busy`` intervals inside ``[lo, hi]``."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def timeline(spans: list[tuple[str, float, float]], lo: float,
             hi: float) -> list[tuple[float, float, str]]:
    """Cut ``[lo, hi]`` into pieces, each labelled with the innermost
    harness span (the latest started one still open) that covers it.

    Host spans of one thread nest, so a stack sweep over their starts and
    ends gives the innermost span of every piece."""
    events = []
    for i, (name, s, e) in enumerate(spans):
        if name != WINDOW_SPAN and e > lo and s < hi:
            events.append((max(s, lo), 1, i, name))
            events.append((min(e, hi), 0, i, name))
    events.sort()
    out, open_, t = [], [], lo
    for when, is_start, i, name in events:
        if when > t:
            out.append((t, when, open_[-1][1] if open_ else UNLABELLED))
            t = when
        if is_start:
            open_.append((i, name))
        else:
            open_ = [x for x in open_ if x[0] != i]
    if hi > t:
        out.append((t, hi, open_[-1][1] if open_ else UNLABELLED))
    return out


def attribute(gap_list, pieces) -> Counter:
    """Seconds of each gap, split over the labelled pieces it overlaps."""
    out: Counter = Counter()
    j = 0
    for s, e in gap_list:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            a, b, label = pieces[k]
            out[label] += (min(b, e) - max(a, s)) * 1e-9
            k += 1
    return out


def reduce_events(device_programs: dict[str, list[tuple[str, float, float]]],
                  host_spans: list[tuple[str, float, float]]) -> TraceSummary:
    """Reduce plain event lists (nanoseconds) to a :class:`TraceSummary`.

    ``device_programs`` maps a device name to its ``(program, start, end)``
    events; ``host_spans`` lists the harness's ``(span, start, end)``.
    """
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo = min(s for s, _ in windows)
    hi = max(e for _, e in windows)
    prog_s: Counter = Counter()
    calls: Counter = Counter()
    idle: Counter = Counter()
    pieces = timeline(host_spans, lo, hi)
    busy_total, n_dev = 0.0, 0
    for events in device_programs.values():
        inside = [(n, s, e) for n, s, e in events if e > lo and s < hi]
        if not inside:
            continue
        n_dev += 1
        for n, s, e in inside:
            prog_s[n] += (min(e, hi) - max(s, lo)) * 1e-9
            calls[n] += 1
        busy = union(clip([(s, e) for _, s, e in inside], lo, hi))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        idle.update(attribute(gaps(busy, lo, hi), pieces))
    n_dev = max(n_dev, 1)
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total / n_dev,
        program_s=dict(prog_s), program_calls=dict(calls),
        idle_by_span={k: v / n_dev for k, v in idle.items()})


def read_xplane(path: str, span_names) -> TraceSummary:
    """Reduce one ``.xplane.pb`` file (see :func:`reduce_events`)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    wanted = set(span_names) | {WINDOW_SPAN}
    devices: dict[str, list[tuple[str, float, float]]] = {}
    spans: list[tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    evs.extend((program_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events if e.name in wanted)
    return reduce_events(devices, spans)


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` that ``jax.profiler`` wrote under a dir."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {len(found)}")
    return found[0]
