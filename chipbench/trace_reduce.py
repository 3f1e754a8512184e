"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device programs that ran, each labelled by the
benchmark's own host span it was enqueued from; the device's busy time;
the programs that took most time; and the longest idle gaps, by what the
host was doing.

Device programs are the events of the ``XLA Modules`` line of each
``/device:TPU:<n>`` plane.  A program is tied to the host call that
enqueued it through ``run_id``: the host's ``DoEnqueueProgram`` event
carries the same id.  Host spans are the benchmark's
``jax.profiler.TraceAnnotation`` names, which all start with ``SPAN``.
Times are nanoseconds from the start of the trace, on one clock for
host and device.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

SPAN = "bench:"
_HASH = re.compile(r"\(\d+\)$")
IDLE_NO_SPAN = "no benchmark span (router idle or waiting for arrivals)"


@dataclass
class Program:
    name: str                 # module name without its hash
    device: int
    start: float              # ns
    dur: float                # ns
    run_id: Optional[str]
    span: Optional[str] = None    # innermost enqueuing benchmark span


def _stats(ev) -> Dict[str, str]:
    return {k: str(v) for k, v in ev.stats}


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def programs_and_spans(pd) -> Tuple[List[Program], List[Tuple[float, float,
                                                               str]]]:
    progs: List[Program] = []
    enq: Dict[str, float] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                for ev in line.events:
                    st = _stats(ev)
                    progs.append(Program(_HASH.sub("", ev.name), dev,
                                         float(ev.start_ns),
                                         float(ev.duration_ns),
                                         st.get("run_id")))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "DoEnqueueProgram":
                        rid = _stats(ev).get("run_id")
                        if rid is not None:
                            enq[rid] = float(ev.start_ns)
                    elif ev.name.startswith(SPAN):
                        s = float(ev.start_ns)
                        spans.append((s, s + float(ev.duration_ns), ev.name))
    spans.sort()
    longest = max((e - s for s, e, _ in spans), default=0.0)
    for p in progs:
        t = enq.get(p.run_id)
        if t is not None:
            p.span = innermost(spans, t, longest)
    progs.sort(key=lambda p: p.start)
    return progs, spans


def innermost(spans, t: float, longest: Optional[float] = None
              ) -> Optional[str]:
    """Name of the shortest span containing ``t`` (``spans`` sorted;
    ``longest`` is the longest span's length, found if not given)."""
    if longest is None:
        longest = max((e - s for s, e, _ in spans), default=0.0)
    best, best_len = None, None
    i = bisect.bisect_right(spans, (t, float("inf"), ""))
    while i > 0:
        i -= 1
        s, e, name = spans[i]
        if s < t - longest:
            break
        if t <= e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def merged(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(pd, window_s: float, top: int = 10) -> dict:
    """-> {"programs", "spans", "busy_s", "window_s", "device_ops",
    "idle_gaps"}.  ``busy_s`` is the union of program intervals,
    averaged over the devices that ran any."""
    progs, spans = programs_and_spans(pd)
    devices = sorted({p.device for p in progs})
    busy = {}
    for d in devices:
        iv = merged([(p.start, p.start + p.dur) for p in progs
                     if p.device == d])
        busy[d] = iv
    busy_s = (sum(sum(e - s for s, e in busy[d]) for d in devices)
              / len(devices) / 1e9) if devices else 0.0

    by_op: Dict[str, float] = {}
    for p in progs:
        key = p.name + (f" @ {p.span[len(SPAN):]}" if p.span else "")
        by_op[key] = by_op.get(key, 0.0) + p.dur / 1e9
    device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]

    gaps: Dict[str, float] = {}
    longest = max((e - s for s, e, _ in spans), default=0.0)
    if devices:
        iv = busy[devices[0]]
        for (_, e0), (s1, _) in zip(iv, iv[1:]):
            name = innermost(spans, (e0 + s1) / 2, longest)
            key = name[len(SPAN):] if name else IDLE_NO_SPAN
            gaps[key] = gaps.get(key, 0.0) + (s1 - e0) / 1e9
    idle_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"programs": progs, "spans": spans, "busy_s": busy_s,
            "window_s": float(window_s),
            "device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps]}


def time_of(programs: List[Program], name_re: str,
            span: Optional[str] = None) -> Tuple[float, int]:
    """Total device seconds and count of programs whose name matches
    ``name_re`` (and, if given, whose enqueuing span is ``SPAN + span``)."""
    rx = re.compile(name_re)
    sel = [p for p in programs if rx.search(p.name)
           and (span is None or p.span == SPAN + span)]
    return sum(p.dur for p in sel) / 1e9, len(sel)
