"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

``jax.profiler.ProfileData`` reads the file; nothing here touches a
device.  Device operations are the events of the ``XLA Ops`` line of
each ``/device:*`` plane.  Where the trace has no device plane (the
CPU backend, in tests) they are the host events that carry an
``hlo_module`` stat.  Each operation is attributed to its program (the
``hlo_module`` stat, or the ``XLA Modules`` event around it), and each
call of a program is counted once.

The traced window is the host span named ``WINDOW`` that the harness
opens after the trace starts and closes before it stops.  All numbers
are clipped to it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Op:
    start: int          # ns
    end: int
    name: str
    program: str
    call: object        # hashable id of the program call it belongs to
    device: str


@dataclasses.dataclass
class Reduced:
    window: Tuple[int, int]                    # ns, trace time base
    n_devices: int
    busy_ns: float                             # union of op intervals, mean over devices
    program_ns: Dict[str, float]               # program -> union of its ops' intervals
    program_calls: Dict[str, int]              # program -> calls seen
    op_ns: Dict[str, float]                    # "program:op" -> summed duration
    gaps: List[Tuple[str, float]]              # longest idle gaps, named by host span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except Exception:        # stats the reader cannot decode
        return {}


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(ivs: List[Tuple[int, int]]) -> float:
    return float(sum(e - s for s, e in ivs))


def _clip(ivs, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def device_ops(pd) -> List[Op]:
    ops: List[Op] = []
    planes = [p for p in pd.planes if p.name.startswith("/device:")]
    for p in planes:
        lines = {ln.name: ln for ln in p.lines}
        if "XLA Ops" not in lines:
            continue
        mods = sorted(((e.start_ns, e.end_ns, e.name)
                       for e in lines["XLA Modules"].events)
                      if "XLA Modules" in lines else [])
        mi = 0
        for e in sorted(lines["XLA Ops"].events, key=lambda e: e.start_ns):
            st = _stats(e)
            while mi < len(mods) and mods[mi][1] < e.start_ns:
                mi += 1
            enclosing = (mods[mi] if mi < len(mods)
                         and mods[mi][0] <= e.start_ns else None)
            program = str(st.get("hlo_module") or (
                enclosing[2] if enclosing else "?"))
            call = (enclosing[0] if enclosing else st.get("run_id"))
            # TPU op events are named by their HLO text: keep the name
            name = e.name.split(" = ", 1)[0].lstrip("%")
            ops.append(Op(int(e.start_ns), int(e.end_ns), name,
                          _SUFFIX.sub("", program), (p.name, call), p.name))
    if ops:
        return ops
    # no device plane: the CPU backend runs its ops on host threads.
    for p in pd.planes:
        for ln in p.lines:
            for e in ln.events:
                st = _stats(e)
                if "hlo_module" in st and e.duration_ns > 0:
                    ops.append(Op(int(e.start_ns), int(e.end_ns), e.name,
                                  str(st["hlo_module"]),
                                  (p.name, st.get("run_id")), p.name))
    return ops


def host_spans(pd) -> List[Tuple[int, int, str]]:
    """Host spans of the serving thread (the host line holding the most
    ``serve.*`` annotations), plus the ``WINDOW`` span wherever it is."""
    lines = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            evs = [(int(e.start_ns), int(e.end_ns), e.name)
                   for e in ln.events
                   if e.duration_ns > 0 and "hlo_module" not in _stats(e)]
            lines.append(evs)
    window = [sp for evs in lines for sp in evs if sp[2] == WINDOW]
    served = max(lines, default=[], key=lambda evs: sum(
        sp[2].startswith("serve.") for sp in evs))
    if not any(sp[2].startswith("serve.") for sp in served):
        served = [sp for evs in lines for sp in evs]
    return window + [sp for sp in served if sp[2] != WINDOW]


def _name_gap(s: int, e: int, spans) -> str:
    """The shortest serving-thread span that covers the gap's middle."""
    mid = (s + e) // 2
    cover = [sp for sp in spans if sp[0] <= mid <= sp[1]
             and sp[2] != WINDOW]
    if cover:
        return min(cover, key=lambda sp: sp[1] - sp[0])[2]
    return "(no host span)"


def reduce(pd, top: int = 10) -> Reduced:
    spans = host_spans(pd)
    win = [(s, e) for s, e, n in spans if n == WINDOW]
    ops = device_ops(pd)
    if win:
        lo, hi = win[0]
    elif ops:
        lo, hi = min(o.start for o in ops), max(o.end for o in ops)
    else:
        raise ValueError("trace holds neither a window span nor device ops")
    ops = [o for o in ops if o.end > lo and o.start < hi]
    devices = sorted({o.device for o in ops})
    busy, gaps = 0.0, []
    for dev in devices:
        u = union(_clip([(o.start, o.end) for o in ops if o.device == dev],
                        lo, hi))
        busy += _length(u)
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    by_prog: Dict[str, List[Tuple[int, int]]] = {}
    calls: Dict[str, set] = {}
    op_ns: Dict[str, float] = {}
    for o in ops:
        by_prog.setdefault(o.program, []).append((o.start, o.end))
        calls.setdefault(o.program, set()).add(o.call)
        key = f"{o.program}:{o.name}"
        op_ns[key] = op_ns.get(key, 0.0) + (min(o.end, hi) - max(o.start, lo))
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return Reduced(
        window=(lo, hi), n_devices=max(len(devices), 1),
        busy_ns=busy / max(len(devices), 1),
        program_ns={k: _length(union(_clip(v, lo, hi)))
                    for k, v in by_prog.items()},
        program_calls={k: len(v) for k, v in calls.items()},
        op_ns=op_ns,
        gaps=[(_name_gap(s, e, spans), (e - s) / 1e9)
              for s, e in gaps[:top]])


def program(red: Reduced, name: str) -> Optional[str]:
    """The program in the trace whose name is ``name`` or ``jit_<name>``,
    or None."""
    for k in red.program_ns:
        if k in (name, f"jit_{name}"):
            return k
    return None


def breakdown(red: Reduced, top: int = 10) -> Dict[str, list]:
    ops = sorted(red.op_ns.items(), key=lambda kv: kv[1], reverse=True)
    return {"device_ops": [[k, v / 1e9] for k, v in ops[:top]],
            "idle_gaps": [[n, s] for n, s in red.gaps[:top]]}
