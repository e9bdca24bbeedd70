"""Per-layer numbers of one traced window from the program's own spans.

The program marks the steps of its loop with host spans named ``loop.*``,
``server.*`` and ``client.*`` (``repro.utils.trace``; README, "Tracing"),
some with stats: ``B``, ``reads``, ``h2d_bytes``, ``d2h_bytes``. They
land on the profiler's host clock, which is also the clock of the
benchmark's ``pb.*`` spans and of the ``CompleteCallbacks`` events by which
:mod:`pb_trace` ties device work to the host. The reduction of a window:

* per drain (``pb.server`` span): the server's staging time
  (``server.flatten`` + ``server.unflatten``), its blocking device reads
  (``server.sync``), and the device time of the named fedagg kernels in
  the executions completed inside the drain spans;
* per update (Σ ``B`` of ``server.drain``): the server's blocking reads
  (Σ ``reads`` of ``server.sync``), the client's staging time
  (``client.stage``) and the bytes every span moved between host and
  device (Σ ``h2d_bytes`` + ``d2h_bytes``);
* the device's idle gaps, each labelled ``<coarse>:<span>``: the coarse
  label of :func:`pb_trace.label_at` and the innermost program span open
  at the gap's middle, or the coarse label alone where none is open.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from pb_trace import gaps, inside, label_at, short, union

PREFIXES = ("loop.", "server.", "client.")
#: the ``name=`` of each fedagg ``pallas_call``
FEDAGG_KERNELS = frozenset((
    "fedagg_norms", "fedagg_axpy", "fedagg_norms_batched",
    "fedagg_apply_batched", "fedagg_fused", "fedagg_norms_q",
    "fedagg_axpy_q", "fedagg_norms_batched_q", "fedagg_apply_batched_q"))
#: an XLA operation named for its kernel: ``%fedagg_axpy.1``
_KERNEL_OP = re.compile(r"[%_]?(fedagg_[a-z_]+?)(?:\.\d+)?")
#: spans of the server's work inside a drain
SERVER_CHILDREN = ("server.flatten", "server.kernels", "server.sync",
                   "server.schedule", "server.book", "server.unflatten")


class Span(NamedTuple):
    name: str
    start: float
    end: float
    stats: dict


def read(trace_dir: str) -> List[Span]:
    """The program's spans in the one trace under ``trace_dir``, host
    clock, ns."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(paths)}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    s = float(ev.start_ns)
                    out.append(Span(ev.name, s, s + float(ev.duration_ns),
                                    dict(ev.stats)))
    return out


def kernel_of(op: str) -> Optional[str]:
    """The fedagg kernel an XLA operation runs, or None."""
    m = _KERNEL_OP.fullmatch(short(op))
    return m.group(1) if m and m.group(1) in FEDAGG_KERNELS else None


def innermost(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces of the timeline, each named
    for the innermost span open over it. Spans nest, as the program's
    ``with`` blocks on one thread do."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Span] = []
    t = float("-inf")

    def emit(upto: float) -> None:
        if stack and upto > t:
            out.append((t, upto, stack[-1].name))

    for sp in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= sp.start:
            emit(stack[-1].end)
            t = max(t, stack.pop().end)
        emit(sp.start)
        stack.append(sp)
        t = sp.start
    while stack:
        emit(stack[-1].end)
        t = max(t, stack.pop().end)
    return out


def span_at(t: float, pieces: Sequence[Tuple[float, float, str]]
            ) -> Optional[str]:
    i = bisect.bisect_right(pieces, (t, float("inf"), "")) - 1
    return pieces[i][2] if i >= 0 and pieces[i][0] <= t < pieces[i][1] \
        else None


def reduce(devices: Dict[str, dict], host: Dict[str, list],
           completed: Dict[int, float], spans: Sequence[Span],
           top: int = 10) -> dict:
    """``devices``, ``host`` and ``completed`` as :func:`pb_trace.read`
    gives them; ``spans`` as :func:`read` does. Seconds and bytes out,
    summed over the window; :func:`per_layer` divides."""
    (lo, hi), = host["pb.window"]
    server = union(host.get("pb.server", []))
    client = union(host.get("pb.client", []))
    win = [s for s in spans if lo <= s.start < hi]
    time: Dict[str, float] = defaultdict(float)
    reads = nbytes = updates = 0
    for s in win:
        time[s.name] += s.end - s.start
        nbytes += s.stats.get("h2d_bytes", 0) + s.stats.get("d2h_bytes", 0)
        if s.name == "server.sync":
            reads += s.stats.get("reads", 0)
        elif s.name == "server.drain":
            updates += s.stats.get("B", 0)
    pieces = innermost(win)

    kernel = 0.0
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
        ops_in = []
        for name, s, e in dev["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or e > mods[i][2] + 1:
                continue
            t = completed.get(mods[i][3], mods[i][2] + shift)
            if not lo <= t <= hi:
                continue
            ops_in.append((s, e))
            if inside(t, server) and kernel_of(name):
                kernel += e - s
        for s, e in gaps(union(ops_in), lo - shift, hi - shift):
            mid = (s + e) / 2 + shift
            coarse = label_at(mid, server, client)
            inner = span_at(mid, pieces)
            what = f"{coarse}:{inner}" if inner else coarse
            idle[what] += e - s
            longest.append((e - s, what))
    n = max(n, 1)
    longest.sort(reverse=True)
    return {
        "drains": sum(1 for s, _ in host.get("pb.server", [])
                      if lo <= s < hi),
        "updates": updates,
        "span_s": {k: v * 1e-9 for k, v in sorted(time.items())},
        "server_reads": reads,
        "host_bytes": nbytes,
        "fedagg_device_s": kernel / n * 1e-9,
        "idle_by_span_s": {k: v / n * 1e-9 for k, v in sorted(idle.items())},
        "idle_gaps": [[what, d * 1e-9] for d, what in longest[:top]],
    }


def server_child_share(r: dict) -> Optional[float]:
    """Share of the device's idle time inside the drain spans that a
    child span of ``server.drain`` labels."""
    server = {k.partition(":")[2]: v for k, v in r["idle_by_span_s"].items()
              if k.partition(":")[0] == "server"}
    total = sum(server.values())
    if not total:
        return None
    return sum(v for k, v in server.items() if k in SERVER_CHILDREN) / total


def per_layer(r: Optional[dict]) -> Dict[str, Optional[float]]:
    """The per-layer metrics of a reduced window, each None where the
    window holds nothing to read: no program spans (a program without
    them) or no drain."""
    names = ("server_stage_ms", "server_sync_ms", "server_syncs_per_update",
             "fedagg_device_ms", "client_stage_ms", "host_bytes_per_update")
    if not r or not r["drains"] or not r["updates"] or not r["span_s"]:
        return dict.fromkeys(names)
    t, d, u = r["span_s"], r["drains"], r["updates"]
    return dict(zip(names, (
        (t.get("server.flatten", 0.0) + t.get("server.unflatten", 0.0))
        / d * 1e3,
        t.get("server.sync", 0.0) / d * 1e3,
        r["server_reads"] / u,
        r["fedagg_device_s"] / d * 1e3,
        t.get("client.stage", 0.0) / u * 1e3,
        r["host_bytes"] / u)))
