#!/usr/bin/env python3
"""Device time by named scope, read from a profiler run's ``.xplane.pb``.

    python3 chipbench/scopes.py <trace dir> [--scopes gather,mix,...]

prints the first device's op seconds by scope as JSON.

The program's compiled steps carry ``jax.named_scope`` names in each HLO
instruction's ``op_name``: ``mega_round`` (the scan step), ``gather``,
``mix``, ``fwd_bwd``, ``adam``, ``write_back`` and ``eval`` on the LM plane,
``sample``, ``mix``, ``sgd`` and ``write_back`` on the sim plane.  A device
op event names its instruction; the profiler keeps each module's HLO proto
in the ``/host:metadata`` plane, and the instruction's ``op_name`` is there.
This joins the two, and charges each leaf op (one that holds no other, as
``devtrace.leaves`` has it) to the innermost listed scope of its path, or
to ``no scope``.  Where an op event carries its path itself, as a ``tf_op``
stat, that is used instead.

``jax.profiler.ProfileData`` does not reach the metadata plane, so the file
is read with the small protobuf reader below (field numbers from
``xplane.proto`` and ``hlo.proto``).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

import devtrace

SCOPES = ("mega_round", "sample", "gather", "mix", "sgd", "fwd_bwd", "adam",
          "write_back", "eval")
NO_SCOPE = "no scope"
_MODULES_LINE = "XLA Modules"


# -- protobuf wire format --------------------------------------------------
def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of one message: an int for a
    varint, the raw bytes for every other wire type."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, v


def _map_entry(b: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for no, v in fields(b):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


# -- HLO protos: instruction name -> op_name, per module -------------------
def hlo_op_names(hlo_proto: bytes) -> Tuple[str, Dict[str, str]]:
    """The module's name and each instruction's ``op_name`` (HloProto ->
    HloModuleProto{name 1, computations 3} -> HloComputationProto
    {instructions 2} -> HloInstructionProto{name 1, metadata 7} ->
    OpMetadata{op_name 2})."""
    name, ops = "", {}
    for no, module in fields(hlo_proto):
        if no != 1:
            continue
        for mno, v in fields(module):
            if mno == 1:
                name = v.decode()
            elif mno == 3:
                for cno, inst in fields(v):
                    if cno != 2:
                        continue
                    iname, op = "", ""
                    for ino, iv in fields(inst):
                        if ino == 1:
                            iname = iv.decode()
                        elif ino == 7:
                            for ono, ov in fields(iv):
                                if ono == 2:
                                    op = ov.decode()
                    ops[iname] = op
    return name, ops


# -- the XSpace --------------------------------------------------------------
class _Plane:
    def __init__(self, b: bytes):
        self.name, self.lines = "", []
        self.event_md: Dict[int, bytes] = {}
        self.stat_names: Dict[int, str] = {}
        for no, v in fields(b):
            if no == 2:
                self.name = v.decode()
            elif no == 3:
                self.lines.append(v)
            elif no == 4:
                k, md = _map_entry(v)
                self.event_md[k] = md
            elif no == 5:
                k, md = _map_entry(v)
                self.stat_names[k] = next(
                    (s.decode() for sno, s in fields(md) if sno == 2), "")
        self._md: Dict[int, tuple] = {}

    def stats(self, raw: List[bytes]) -> Dict[str, object]:
        out = {}
        for st in raw:
            sid, val = 0, None
            for no, v in fields(st):
                if no == 1:
                    sid = v
                elif no in (5, 6):
                    val = v
                elif no == 7:
                    val = self.stat_names.get(v, "").encode()
                elif no in (3, 4):
                    val = v
            out[self.stat_names.get(sid, "")] = val
        return out

    def metadata(self, mid: int) -> tuple:
        """(name, stats) of event metadata ``mid``."""
        if mid not in self._md:
            name, raw = "", []
            for no, v in fields(self.event_md.get(mid, b"")):
                if no == 2:
                    name = v.decode()
                elif no == 5:
                    raw.append(v)
            self._md[mid] = (name, self.stats(raw))
        return self._md[mid]

    def events(self) -> Iterator[tuple]:
        """(line name, metadata name, start_ns, duration_ns, stats)."""
        for line in self.lines:
            lname, t0, evs = "", 0, []
            for no, v in fields(line):
                if no == 2:
                    lname = v.decode()
                elif no == 3:
                    t0 = v
                elif no == 4:
                    evs.append(v)
            for ev in evs:
                mid, off, dur, raw = 0, 0, 0, []
                for no, v in fields(ev):
                    if no == 1:
                        mid = v
                    elif no == 2:
                        off = v
                    elif no == 3:
                        dur = v
                    elif no == 4:
                        raw.append(v)
                name, stats = self.metadata(mid)
                if raw:
                    stats = {**stats, **self.stats(raw)}
                yield lname, name, t0 + off / 1e3, dur / 1e3, stats


def _text(v) -> str:
    return v.decode() if isinstance(v, bytes) else str(v)


def op_paths(xspace: bytes) -> List[list]:
    """[scope path, start_ns, duration_ns] of every op on the first device
    (on a trace with no device, of every host op that names its HLO
    instruction, as a CPU trace's are)."""
    planes = [_Plane(v) for no, v in fields(xspace) if no == 1]
    modules: Dict[str, Dict[str, str]] = {}
    for pl in planes:
        if pl.name != "/host:metadata":
            continue
        for mid in pl.event_md:
            name, stats = pl.metadata(mid)
            proto = stats.get("Hlo Proto")
            if isinstance(proto, bytes):
                mod, ops = hlo_op_names(proto)
                modules[mod] = modules[name] = ops
    devs = sorted((pl for pl in planes if devtrace._DEVICE.match(pl.name)),
                  key=lambda pl: pl.name)
    if devs:
        evs = list(devs[0].events())
        ops = [e for e in evs if e[0] == devtrace._OPS_LINE]
        spans = sorted((s, s + d, re.sub(r"\(.*\)$", "", n))
                       for ln, n, s, d, _ in evs if ln == _MODULES_LINE)
    else:
        ops = [e for pl in planes if pl.name.startswith("/host:")
               for e in pl.events() if "hlo_op" in e[4]]
        spans = []
    out = []
    for _, name, start, dur, stats in ops:
        path = stats.get("tf_op")
        if path is None:
            inst = _text(stats.get("hlo_op") or
                         name.split(" = ", 1)[0].lstrip("%"))
            mod = stats.get("hlo_module")
            mod = _text(mod) if mod is not None else _module_at(spans, start)
            path = modules.get(mod, {}).get(inst, "")
        out.append([_text(path), start, dur])
    return out


def _module_at(spans, t: float) -> Optional[str]:
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if t < e:
            best = name
    return best


def innermost(path: str, scopes) -> str:
    parts = path.split("/")
    for part in reversed(parts):
        if part in scopes:
            return part
    return NO_SCOPE


def scope_seconds(xspace: bytes, scopes=SCOPES) -> Dict[str, float]:
    """Leaf-op seconds of the first device by innermost listed scope."""
    out: Dict[str, float] = {}
    for path, _, dur in devtrace.leaves(op_paths(xspace)):
        key = innermost(path, scopes)
        out[key] = out.get(key, 0.0) + dur / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir", help="a jax.profiler trace directory")
    ap.add_argument("--scopes", default=",".join(SCOPES))
    a = ap.parse_args(argv)
    found = sorted(glob.glob(os.path.join(a.trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise SystemExit(f"no .xplane.pb under {a.trace_dir}")
    with open(found[-1], "rb") as f:
        xspace = f.read()
    print(json.dumps(scope_seconds(xspace, tuple(a.scopes.split(",")))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
