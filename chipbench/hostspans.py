"""The program's own host spans beside the device's operations.

``repro.core.trace`` opens a ``jax.profiler.TraceAnnotation`` named
``dystop/<span>`` around each phase of a drive-loop call, so a traced run's
host planes hold them on the device ops' clock.  This reads them from the
form ``devtrace.load`` returns; a trace of a program without them reads
nothing.

"The call's extent" runs from the start of the last ``dystop/setup`` span
(the window's call) to the end of the last ``dystop/`` span after it;
"idle" is that extent less the busy union of the first device's ops.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import devtrace

PREFIX = "dystop/"


def program_spans(trace: dict) -> List[Tuple[str, float, float]]:
    """(span, start_ns, end_ns) of every ``dystop/`` host event, by start."""
    return sorted((name[len(PREFIX):], s, s + d)
                  for name, s, d in devtrace.host_events(trace)
                  if name.startswith(PREFIX))


def call_extent(spans) -> Optional[Tuple[float, float]]:
    starts = [s for name, s, _ in spans if name == "setup"]
    if not starts:
        return None
    lo = max(starts)
    return lo, max(e for _, s, e in spans if s >= lo)


def idle_intervals(trace: dict, lo: float, hi: float):
    """The intervals of [lo, hi) in which the first device ran no op; None
    where the trace has no device."""
    devs = devtrace.device_events(trace)
    if not devs:
        return None
    out, cur = [], lo
    for s, e in devtrace.union(devs[sorted(devs)[0]]):
        if e <= cur:
            continue
        if s >= hi:
            break
        if s > cur:
            out.append((cur, s))
        cur = e
    if cur < hi:
        out.append((cur, hi))
    return out


def covered(intervals, spans) -> float:
    """How much of the sorted, disjoint ``intervals`` the union of the
    (start, end) ``spans`` covers, in ns."""
    cover = devtrace.union([[None, s, e - s] for s, e in spans])
    total, j = 0.0, 0
    for lo, hi in intervals:
        while j < len(cover) and cover[j][1] <= lo:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < hi:
            total += min(hi, cover[k][1]) - max(lo, cover[k][0])
            k += 1
    return total


def idle_share(trace: dict, names=None) -> Optional[float]:
    """Share (%) of the device's idle time over the call's extent in which
    a ``dystop/`` span was open on the host (one of ``names``, or any);
    None where the trace has no such span, no device or no idle time."""
    spans = program_spans(trace)
    extent = call_extent(spans)
    if extent is None:
        return None
    idle = idle_intervals(trace, *extent)
    total = sum(e - s for s, e in idle or ())
    if total <= 0:
        return None
    open_ = [(s, e) for name, s, e in spans
             if names is None or name in names]
    return 100.0 * covered(idle, open_) / total
