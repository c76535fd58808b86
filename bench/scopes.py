"""Device time by round pass, and idle gaps named by the program's spans.

The program marks every device operation of a round with the pass it
belongs to: the outermost ``sssp.*`` named scope in the operation's
``op_name`` (``sssp.relax``, ``sssp.lb``, ``sssp.inw``, ``sssp.fix``,
``sssp.frontier``, ``sssp.freeze``, ``sssp.count``, ``sssp.taint``,
``sssp.init``; docs/round-anatomy.md).  On the TPU the trace carries
that ``op_name`` as the ``tf_op`` stat of each ``XLA Ops`` event's
metadata, which :func:`op_names` reads from the ``.xplane.pb`` itself
(a few dozen lines of protobuf wire format: no generated classes).
:func:`reduce` attributes each operation's own time (a loop's body
taken out of the loop, as :func:`bench.trace.self_times` does) to its
pass, or to ``unscoped``, and prefixes the top operations' labels with
it (``sssp.inw|%fusion.96 fusion``).  A fusion takes its root's pass;
where its fused instructions come from more than one pass (read from
the optimized HLO text, :func:`fused_scopes`) it is listed as
``mixed``.  An idle gap keeps the harness span over its midpoint and
adds the innermost program span there (``serve>sssp.answer``).

Busy time, window and idle share come from :func:`bench.trace.reduce`
unchanged.  ``python3 bench/passes.py`` takes such a trace of one cell.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

from bench import trace

SCOPE = re.compile(r"sssp\.[a-z]+")   # also inside ``vmap(sssp.init)``
UNSCOPED = "unscoped"
PROGRAM = "sssp."                     # the program's host spans
ARC = ("sssp.relax", "sssp.lb", "sssp.inw")
VERTEX = ("sssp.fix", "sssp.frontier", "sssp.freeze", "sssp.count")
_FUSION = re.compile(r"(%\S+ = .*?) fusion\(.*\), kind=(\w+), "
                     r"calls=%([\w.\-]+)")


@dataclasses.dataclass
class ScopedReduction(trace.Reduction):
    scope_s: dict = dataclasses.field(default_factory=dict)
    # {pass: seconds of device time}, ``unscoped`` included
    mixed: list = dataclasses.field(default_factory=list)
    # [[label, seconds, [passes]], ...] fusions over several passes
    idle_s: dict = dataclasses.field(default_factory=dict)
    # {idle-gap label: seconds}, every gap of the window


def scope_of(op_name: str | None) -> str:
    m = SCOPE.search(op_name or "")
    return m.group(0) if m else UNSCOPED


# --- the op_name of each device operation, from the .xplane.pb ------

def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b, start=0, end=None):
    """``(field, value)`` of one message; a length-delimited value is
    its ``(start, end)`` in ``b``."""
    i, end = start, len(b) if end is None else end
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = bytes(b[i:i + n]), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at {i}")
        yield key >> 3, v


def _map_value(b, span):
    """``(key, value span)`` of a protobuf map entry."""
    key, val = 0, None
    for f, v in _fields(b, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def op_names(path: str) -> dict:
    """``{device plane: {operation name: op_name}}`` from the ``tf_op``
    stat of each device plane's event metadata (``XSpace.planes``:
    name 2, event_metadata 4, stat_metadata 5; ``XEventMetadata``:
    name 2, stats 5; ``XStat``: metadata_id 1, str_value 5,
    ref_value 7)."""
    with open(path, "rb") as f:
        b = memoryview(f.read())
    out = {}
    for f, plane in _fields(b):
        if f != 1:
            continue
        name, events, stats = "", [], {}
        for pf, v in _fields(b, *plane):
            if pf == 2:
                name = _text(b, v)
            elif pf == 4:
                events.append(v)
            elif pf == 5:
                key, val = _map_value(b, v)
                stats[key] = next((_text(b, sv) for sf, sv in
                                   _fields(b, *val) if sf == 2), "")
        if not name.startswith("/device:"):
            continue
        tf_op = {k for k, s in stats.items() if s == "tf_op"}
        names = {}
        for entry in events:
            _, md = _map_value(b, entry)
            op, op_name = None, None
            for mf, mv in _fields(b, *md):
                if mf == 2:
                    op = _text(b, mv)
                elif mf == 5:
                    stat = dict(_fields(b, *mv))
                    if stat.get(1) in tf_op:
                        op_name = (_text(b, stat[5]) if 5 in stat
                                   else stats.get(stat.get(7), ""))
            if op is not None and op_name is not None:
                names[op] = op_name
        out[name] = names
    return out


# --- fusions over several passes, from the optimized HLO text -------

def _head(instruction: str) -> tuple | None:
    """``(name and shape, kind, fused computation)`` of a fusion: what
    the trace's operation name (operands with their shapes) and the HLO
    dump's line (operands without) share."""
    m = _FUSION.match(instruction.strip().removeprefix("ROOT "))
    return m.groups() if m else None


def fused_scopes(texts) -> dict:
    """``{fusion head: {passes of its fused instructions}}`` over the
    optimized HLO modules ``texts``."""
    out = {}
    for text in texts:
        comps, heads, cur = {}, set(), None
        for line in text.splitlines():
            s = line.strip()
            if s.endswith("{") and not s.startswith("ROOT"):
                cur = s.split(" ", 1)[0].lstrip("%")
                comps[cur] = set()
            elif s == "}":
                cur = None
            elif cur is not None:
                m = re.search(r'op_name="([^"]*)"', s)
                if m:
                    comps[cur].add(scope_of(m.group(1)))
                h = _head(s) if " fusion(" in s else None
                if h:
                    heads.add(h)
        for h in heads:
            out.setdefault(h, set()).update(comps.get(h[2], ()))
    return out


def hlo_texts(dump_dir: str):
    """The optimized modules of an ``--xla_dump_to`` directory."""
    for p in sorted(glob.glob(os.path.join(
            dump_dir, "*after_optimizations.txt"))):
        with open(p) as f:
            yield f.read()


# --- the reduction ---------------------------------------------------

def reduce(dev: dict, host: list, names: dict,
           fusions: dict | None = None) -> ScopedReduction:
    """:func:`bench.trace.reduce` plus device time by pass.

    ``dev`` and ``host`` as :func:`bench.trace.events` gives them, but
    ``host`` may hold the program's ``sssp.*`` spans too; ``names``
    maps each device plane's operation names to their ``op_name``
    (:func:`op_names`); ``fusions`` is :func:`fused_scopes`' output.
    """
    harness = [h for h in host if not h[0].startswith(PROGRAM)]
    base = trace.reduce(dev, harness)
    w0, w1 = max(((s, e) for n, s, e in host if n == trace.WINDOW),
                 key=lambda x: x[1] - x[0])
    totals, busy_union = {}, None
    for plane, ops in sorted(dev.items()):
        plane_names = names.get(plane, {})
        clipped = [(f"{scope_of(plane_names.get(n))}|{n}", max(s, w0),
                    min(e, w1)) for n, s, e in ops if e > w0 and s < w1]
        if not clipped:
            continue
        for label, t in trace.self_times(clipped).items():
            totals[label] = totals.get(label, 0.0) + t
        if busy_union is None:
            busy_union = trace._union([(s, e) for _, s, e in clipped])
    n_dev = max(base.devices, 1)
    scope_s, mixed = {}, []
    for label, t in totals.items():
        scope = label.partition("|")[0]
        scope_s[scope] = scope_s.get(scope, 0.0) + t / n_dev
    if fusions:
        heads = {}
        for plane, ops in dev.items():
            plane_names = names.get(plane, {})
            for n, _, _ in ops:
                h = _head(n)
                if h in fusions:
                    label = trace.op_label(
                        f"{scope_of(plane_names.get(n))}|{n}")
                    heads[label] = fusions[h] - {UNSCOPED}
        mixed = sorted(([label, totals[label] / n_dev, sorted(p)]
                        for label, p in heads.items()
                        if len(p) > 1 and label in totals),
                       key=lambda x: -x[1])
    ops = sorted(([n, t] for n, t in totals.items()), key=lambda x: -x[1])
    gaps = _gap_labels(busy_union or [], host, w0, w1)
    idle_s = {}
    for label, t in gaps:
        idle_s[label] = idle_s.get(label, 0.0) + t
    return ScopedReduction(
        window_s=base.window_s, busy_s=base.busy_s, devices=base.devices,
        device_ops=ops[:trace.TOP], idle_gaps=gaps[:trace.TOP],
        scope_s=scope_s, mixed=mixed, idle_s=idle_s)


def _gap_labels(busy, host, w0, w1):
    """Every idle gap, longest first, named ``<harness span>`` or, where
    a program span covers its midpoint, ``<harness span>><innermost
    program span>``."""
    spans = sorted((s, e, n) for n, s, e in host if n != trace.WINDOW
                   and e > w0 and s < w1)
    named, cursor = [], w0
    for s, e in list(busy) + [[w1, w1]]:
        if s > cursor:
            mid = (cursor + s) / 2
            outer, inner = "harness", None
            for ss, se, sn in spans:      # sorted by start: last = innermost
                if ss <= mid <= se:
                    if sn.startswith(PROGRAM):
                        inner = sn
                    else:
                        outer = sn
            named.append([outer if inner is None else f"{outer}>{inner}",
                          (s - cursor) / 1e9])
        cursor = max(cursor, e)
    named.sort(key=lambda x: -x[1])
    return named


def host_spans(path: str) -> list:
    """``[(name, start, end)]`` of the harness's and the program's spans
    on the host planes."""
    from jax.profiler import ProfileData

    wanted = set(trace.SPANS) | {trace.WINDOW}
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name in wanted or e.name.startswith(PROGRAM)]


def reduce_file(path: str, dump_dir: str | None = None) -> ScopedReduction:
    dev, _ = trace.events(path)
    fusions = fused_scopes(hlo_texts(dump_dir)) if dump_dir else None
    return reduce(dev, host_spans(path), op_names(path), fusions)


# --- what the per-pass readers share ---------------------------------

def window_rounds(run) -> int | None:
    """Engine rounds behind the traced window: each batch's rounds (the
    most over its lanes), or the service's ``query_rounds`` plus
    ``delta_rounds``."""
    if run.kind == "batch":
        rounds = getattr(run.records, "rounds", None)
        return int(sum(np.max(r) for r in rounds)) if rounds else None
    c = getattr(run.records, "counters", None) or {}
    if "query_rounds" not in c or "delta_rounds" not in c:
        return None
    return int(c["query_rounds"] + c["delta_rounds"])


def pass_ms(run, passes) -> float | None:
    """Device time of ``passes`` per round over the traced window."""
    scope_s = getattr(run.reduction, "scope_s", None)
    rounds = window_rounds(run)
    if not scope_s or not rounds:
        return None
    return 1e3 * sum(scope_s.get(p, 0.0) for p in passes) / rounds
