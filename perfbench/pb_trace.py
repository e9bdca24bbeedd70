"""From a profiler trace to the device numbers of one window.

The run wraps its window in a ``pb.window`` host annotation and each
drain and client fan-out in ``pb.server`` / ``pb.client``. On a TPU the
profiler's device clock is offset from its host clock by about a
millisecond, so device work is tied to the host by run id, not by time:
each program execution on the device (``XLA Modules``) carries a
``run_id`` that the host's ``CompleteCallbacks`` event for it carries too.
Every wrapped span ends when its work is ready, so an execution belongs
to the span in which the host saw it complete. The reduction:

* busy: the union of the device-operation intervals (``XLA Ops``) of the
  executions completed inside the window, averaged over the devices;
* busy inside the server spans: the same for the executions completed
  inside ``pb.server`` spans;
* idle gaps: the holes in that union, each labelled with what the host was
  doing at its middle (``server``, ``client`` or ``loop`` -- neither: the
  event loop, evaluation, behaviour draws), the device clock moved onto
  the host's by the smallest completion delay seen;
* the device operations that took most time, by program and operation.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

HOST_SPANS = ("pb.window", "pb.server", "pb.client")
COMPLETE = "CompleteCallbacks"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return out


def inside(t: float, spans: Sequence[Interval]) -> bool:
    """Whether ``t`` lies in one of the sorted, disjoint ``spans``."""
    i = bisect.bisect_right(spans, (t, float("inf"))) - 1
    return i >= 0 and spans[i][0] <= t < spans[i][1]


def label_at(t: float, server: Sequence[Interval],
             client: Sequence[Interval]) -> str:
    if inside(t, server):
        return "server"
    if inside(t, client):
        return "client"
    return "loop"


def short(name: str) -> str:
    """An HLO operation's name without its shapes and operands."""
    return name.split(" = ", 1)[0]


def reduce(devices: Dict[str, dict], host: Dict[str, List[Interval]],
           completed: Dict[int, float], top: int = 10) -> dict:
    """``devices``: per device, ``modules`` -- ``(name, start, end,
    run_id)`` -- and ``ops`` -- ``(name, start, end)``, device clock, ns.
    ``host``: per span name, ``(start, end)``; ``completed``: run id ->
    host time the host saw it complete; host clock, ns. Seconds out."""
    (lo, hi), = host["pb.window"]
    server = union(host.get("pb.server", []))
    client = union(host.get("pb.client", []))
    busy_total = busy_server = 0.0
    per_op: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    longest: List[tuple] = []
    n = 0
    for dev in devices.values():
        mods = sorted(dev["modules"], key=lambda m: m[1])
        if not mods:
            continue
        n += 1
        delays = [completed[m[3]] - m[2] for m in mods if m[3] in completed]
        shift = min(delays) if delays else 0.0
        starts = [m[1] for m in mods]
        ops_in, ops_server = [], []
        for name, s, e in dev["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or e > mods[i][2] + 1:
                continue
            mname, _, _, run = mods[i]
            t = completed.get(run, mods[i][2] + shift)
            if not lo <= t <= hi:
                continue
            ops_in.append((s, e))
            if inside(t, server):
                ops_server.append((s, e))
            per_op[f"{mname.split('(')[0]}/{short(name)}"] += e - s
        busy = union(ops_in)
        busy_total += length(busy)
        busy_server += length(union(ops_server))
        for s, e in gaps(busy, lo - shift, hi - shift):
            what = label_at((s + e) / 2 + shift, server, client)
            idle[what] += e - s
            longest.append((e - s, what))
    n = max(n, 1)
    longest.sort(reverse=True)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / n * 1e-9,
        "busy_in_server_s": busy_server / n * 1e-9,
        "idle_by_host_s": {k: v / n * 1e-9 for k, v in idle.items()},
        "device_ops": [[k, v / n * 1e-9] for k, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[what, d * 1e-9] for d, what in longest[:top]],
    }


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:
        return {}


def read(trace_dir: str):
    """``(devices, host spans, completions)`` of the one trace under
    ``trace_dir``, in the form :func:`reduce` takes."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices: Dict[str, dict] = {}
    host: Dict[str, List[Interval]] = defaultdict(list)
    completed: Dict[int, float] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        s = float(ev.start_ns)
                        dev["modules"].append(
                            (ev.name, s, s + float(ev.duration_ns),
                             _stats(ev).get("run_id")))
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        s = float(ev.start_ns)
                        dev["ops"].append((ev.name, s,
                                           s + float(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        s = float(ev.start_ns)
                        host[ev.name].append((s, s + float(ev.duration_ns)))
                    elif ev.name == COMPLETE:
                        run = _stats(ev).get("run_id")
                        if run is not None:
                            completed[run] = float(ev.start_ns)
    return devices, dict(host), completed
