"""Reduce a profiler trace to device busy time, top device operations
and idle gaps named by the harness span that covered them.

The harness wraps its measured window in a ``window`` span and each
call into the program in a span of its own (:data:`SPANS`), all with
``jax.profiler.TraceAnnotation``, so they land in the trace on the
host's clock beside the device's operations.  Busy time is the union
of the intervals in which an operation ran on a device's ``XLA Ops``
line (a ``while`` loop's operation spans its body), clipped to the
window and averaged over the devices that ran any; an idle gap is a
stretch of the window in which none ran.  The top operations are
ranked by their own time, a loop's body taken out of the loop.
"""
from __future__ import annotations

import dataclasses
import glob
import os

SPANS = ("serve", "apply_delta", "solve_batch", "fetch", "wait")
WINDOW = "window"
OPS_LINE = "XLA Ops"
TOP = 10


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                  # mean over devices with operations
    devices: int
    device_ops: list               # [[name, seconds], ...] longest first
    idle_gaps: list                # [[span, seconds], ...] longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def op_label(hlo: str) -> str:
    """``"%fusion.89 fusion"`` from an ``XLA Ops`` event's HLO text."""
    name, _, rest = hlo.partition(" = ")
    if rest.startswith("("):             # tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return f"{name} {rest.strip().partition('(')[0]}".strip()


def self_times(ops):
    """``{label: seconds}`` of each operation's own time: a ``while``
    or ``conditional`` holds the operations of its body, whose time is
    subtracted from it."""
    out = {}
    stack = []          # [end, label, own ns]
    for name, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            end, label, own = stack.pop()
            out[label] = out.get(label, 0.0) + own / 1e9
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, op_label(name), e - s])
    for end, label, own in stack:
        out[label] = out.get(label, 0.0) + own / 1e9
    return out


def events(path: str):
    """``(device_ops, host_spans)`` from an ``.xplane.pb``:
    ``device_ops`` maps a device plane to ``[(name, start, end)]`` (ns)
    of its ``XLA Ops`` line; ``host_spans`` is ``[(name, start, end)]``
    of the harness's spans on the host planes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev, host = {}, []
    wanted = set(SPANS) | {WINDOW}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name in wanted)
    return dev, host


def reduce(dev: dict, host: list) -> Reduction:
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        raise ValueError("the trace holds no 'window' span")
    w0, w1 = max(windows, key=lambda x: x[1] - x[0])
    spans = sorted((s, e, n) for n, s, e in host if n != WINDOW
                   and e > w0 and s < w1)
    per_dev_busy, totals = [], {}
    busy_union = None
    for plane, ops in sorted(dev.items()):
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                   if e > w0 and s < w1]
        if not clipped:
            continue
        for label, t in self_times(clipped).items():
            totals[label] = totals.get(label, 0.0) + t
        u = _union([(s, e) for _, s, e in clipped])
        per_dev_busy.append(sum(e - s for s, e in u) / 1e9)
        if busy_union is None:
            busy_union = u
    gaps = []
    cursor = w0
    for s, e in (busy_union or []) + [[w1, w1]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        name = "harness"
        for ss, se, sn in spans:
            if ss <= mid <= se:
                name = sn          # innermost: spans sorted by start
        named.append([name, (e - s) / 1e9])
    named.sort(key=lambda x: -x[1])
    ops = sorted(([n, t] for n, t in totals.items()), key=lambda x: -x[1])
    return Reduction(
        window_s=(w1 - w0) / 1e9,
        busy_s=(sum(per_dev_busy) / len(per_dev_busy)
                if per_dev_busy else 0.0),
        devices=len(per_dev_busy), device_ops=ops[:TOP],
        idle_gaps=named[:TOP])


def reduce_file(path: str) -> Reduction:
    return reduce(*events(path))
