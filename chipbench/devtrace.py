"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists: ``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}``.  Everything below works on that form,
so the CPU tests can feed it a small recorded trace kept as JSON.

- busy time: the union of the intervals in which an operation ran on a
  device, averaged over the devices;
- kernel time: the summed durations of the device ops named after the
  kernel;
- breakdown: the device operations that took most time (an op that holds
  others, as a ``while`` holds its body, is left out for them), and the
  longest idle gaps, each named by the innermost host event that covers
  most of it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
# the per-operation line of a device plane; other lines (modules, steps)
# repeat the same time at a coarser grain
_OPS_LINE = "XLA Ops"


def load(trace_dir: str) -> dict:
    import jax
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(found[-1])
    planes = []
    for pl in data.planes:
        lines = []
        for ln in pl.lines:
            lines.append({"name": ln.name,
                          "events": [[e.name, float(e.start_ns),
                                      float(e.duration_ns)]
                                     for e in ln.events]})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def device_events(trace: dict) -> Dict[str, List[list]]:
    """Per device plane, the events of its per-operation line (or of all its
    lines where it has none)."""
    out = {}
    for pl in trace["planes"]:
        if not _DEVICE.match(pl["name"]):
            continue
        ops = [ln for ln in pl["lines"] if ln["name"] == _OPS_LINE]
        lines = ops or pl["lines"]
        out[pl["name"]] = [e for ln in lines for e in ln["events"]]
    return out


def host_events(trace: dict) -> List[list]:
    return [e for pl in trace["planes"] if pl["name"].startswith("/host:")
            for ln in pl["lines"] for e in ln["events"]]


def union(events: List[list]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals, in ns, sorted."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: List[Tuple[float, float]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def busy_s(trace: dict) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    per = [sum(e - s for s, e in union(evs))
           for evs in device_events(trace).values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def op_name(event_name: str) -> str:
    """The operation's own name: a TPU trace names an op by its HLO text,
    ``%dystop_fused_sgd.1 = (...) custom-call(...)``; keep
    ``dystop_fused_sgd``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def kernel_s(trace: dict, kernel: str) -> Optional[float]:
    """Summed device time of the ops named ``kernel``, averaged over the
    devices; None where no such op ran."""
    per = [sum(d for name, _, d in evs if op_name(name) == kernel)
           for evs in device_events(trace).values()]
    total = sum(per)
    return total / len(per) / 1e9 if total > 0 else None


def leaves(events: List[list]) -> List[list]:
    """The events that hold no other: a ``while`` or ``call`` op spans the
    ops of its body on the same line, which would count their time twice."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(evs)
            if i + 1 == len(evs) or evs[i + 1][1] >= e[1] + e[2]]


def breakdown(trace: dict, top: int = 10) -> dict:
    devs = device_events(trace)
    if not devs:
        return {"device_ops": [], "idle_gaps": []}
    first = devs[sorted(devs)[0]]
    by_op: Dict[str, float] = {}
    for name, _, d in leaves(first):
        by_op[op_name(name)] = by_op.get(op_name(name), 0.0) + d / 1e9
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    busy = union(first)
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    hosts = sorted(host_events(trace), key=lambda e: e[1])
    named = []
    for lo, hi in gaps:
        # the most specific host span that covers most of the gap; else the
        # one that overlaps it most
        best, best_key = "no host span", None
        for name, s, d in hosts:
            if s >= hi:
                break
            ov = min(hi, s + d) - max(lo, s)
            if ov <= 0:
                continue
            key = (0, d) if 2 * ov >= hi - lo else (1, -ov)
            if best_key is None or key < best_key:
                best, best_key = name, key
        named.append([best, (hi - lo) / 1e9])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}
