#!/usr/bin/env python3
"""Put a profiler trace's device idle gaps down to what the dispatcher
thread was doing.

    python scripts/trace_gaps.py FILE.xplane.pb [FILE ...]

``benchmark/trace_reduce.py`` names a gap by the device ops on either
side of it, which says where on the device the gap sits.  The served
path's layer spans (docs/observability.md "Layer spans") lie on the same
clock on the host plane: the dispatcher thread is always inside exactly
one of ``dispatch.idle`` (no ticket: the cause is upstream — clients,
handler threads, the GIL), ``dispatch.window`` (the coalescing hold) and
``dispatch.round`` (busy), and inside a round it may be inside
``dispatch.place``, ``dispatch.enqueue`` or ``dispatch.scatter``.  This
gives every gap's seconds to the innermost span(s) that cover it and
prints idle seconds by span and the ten longest gaps.

Two halves, like ``trace_reduce``: reading the file needs jax; the
arithmetic (``flatten``, ``attribute``) takes plain tuples.
"""

from __future__ import annotations

import bisect
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import trace_reduce  # noqa: E402

SPAN_PREFIX = "dispatch."
HOST_PLANE = "/host:CPU"
# what is left of a round outside its three parts: _DISPATCH_LOCK wait,
# packing (np.concatenate, the pow-2 pad), the precheck, future wake-ups
ROUND_REST = "dispatch.round (rest)"
UNNAMED = "(no dispatcher span)"


def host_spans(events: list) -> list:
    """[(name, start_ns, end_ns)] of the dispatcher's layer spans among
    ``trace_reduce.read_events`` tuples."""
    return [(name, start, start + dur)
            for plane, _line, name, start, dur in events
            if plane == HOST_PLANE and name.startswith(SPAN_PREFIX)]


def flatten(spans: list) -> list:
    """Disjoint, sorted [(name, start, end)] covering the same points as
    the nested ``spans``, each stretch under its innermost span's name;
    the part of ``dispatch.round`` outside its children is ROUND_REST."""
    out: list = []
    stack: list = []          # (name, end) of the open spans, outermost first
    cursor = None

    def emit(upto):
        nonlocal cursor
        if upto > cursor:
            name = stack[-1][0]
            out.append((ROUND_REST if name == "dispatch.round" else name,
                        cursor, upto))
            cursor = upto

    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        if stack:
            emit(start)
        else:
            cursor = start if cursor is None else max(cursor, start)
        stack.append((name, end))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def overlap_ns(spans: list, names: tuple) -> float:
    """Nanoseconds in which two spans among ``names`` are open at once
    (0 for one dispatcher thread)."""
    evs = sorted((s, e) for n, s, e in spans if n in names)
    total, reach = 0.0, None
    for s, e in evs:
        if reach is not None and s < reach:
            total += min(e, reach) - s
        reach = e if reach is None else max(reach, e)
    return total


def attribute(gap_list: list, segments: list):
    """Give each gap ``(name, start_ns, dur_ns)`` to the ``flatten``-ed
    segments it overlaps.  Returns ({span: ns}, [(gap name, dur_ns,
    {span: ns})]); what no segment covers goes to UNNAMED."""
    starts = [s for _, s, _ in segments]
    totals: dict = {}
    per_gap = []
    for gname, gstart, gdur in gap_list:
        gend = gstart + gdur
        shares: dict = {}
        covered = 0.0
        i = max(bisect.bisect_right(starts, gstart) - 1, 0)
        while i < len(segments) and segments[i][1] < gend:
            name, s, e = segments[i]
            ov = min(e, gend) - max(s, gstart)
            if ov > 0:
                shares[name] = shares.get(name, 0.0) + ov
                covered += ov
            i += 1
        if gdur - covered > 0:
            shares[UNNAMED] = gdur - covered
        for name, ns in shares.items():
            totals[name] = totals.get(name, 0.0) + ns
        per_gap.append((gname, gdur, shares))
    return totals, per_gap


def dispatch_lines(path: str) -> dict:
    """{(host line index, line name): dispatch.* events on it}."""
    from jax.profiler import ProfileData
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            n = sum(1 for e in line.events if e.name.startswith(SPAN_PREFIX))
            if n:
                out[(i, line.name)] = n
    return out


def report(path: str) -> dict:
    events = trace_reduce.read_events(path)
    spans = host_spans(events)
    segments = flatten(spans)
    gap_list = [g for ops in trace_reduce.device_ops(events).values()
                for g in trace_reduce.gaps(ops)]
    totals, per_gap = attribute(gap_list, segments)
    idle_ns = sum(g[2] for g in gap_list)
    named = idle_ns - totals.get(UNNAMED, 0.0)
    return {
        "file": path,
        "lines": dispatch_lines(path),
        "partition_overlap_s": overlap_ns(
            spans, ("dispatch.idle", "dispatch.window",
                    "dispatch.round")) / 1e9,
        "idle_s": idle_ns / 1e9,
        "named_share": named / idle_ns if idle_ns else None,
        "by_span": sorted(((n, ns / 1e9) for n, ns in totals.items()),
                          key=lambda kv: -kv[1]),
        "longest": [(n, d / 1e9, sorted(((s, ns / 1e9)
                                         for s, ns in sh.items()),
                                        key=lambda kv: -kv[1]))
                    for n, d, sh in sorted(per_gap,
                                           key=lambda g: -g[1])[:10]],
    }


def main(argv=None) -> int:
    paths = (argv if argv is not None else sys.argv[1:])
    if not paths:
        print(__doc__, file=sys.stderr)
        return 2
    for path in paths:
        r = report(path)
        print(f"== {r['file']}")
        print(f"dispatch.* events on host lines: {r['lines']}; idle/window/"
              f"round open at once for {r['partition_overlap_s']:.6f} s")
        if not r["idle_s"]:
            print("no device idle gap")
            continue
        print(f"device idle between ops: {r['idle_s']:.4f} s, "
              f"{100 * r['named_share']:.1f} % under a named span")
        print("| span | idle s | share |")
        print("|---|---|---|")
        for name, s in r["by_span"]:
            print(f"| `{name}` | {s:.4f} | {100 * s / r['idle_s']:.1f} % |")
        print("ten longest gaps:")
        for name, dur, shares in r["longest"]:
            under = ", ".join(f"{n} {1e3 * s:.2f} ms" for n, s in shares)
            print(f"  {1e3 * dur:8.2f} ms  {name}  <- {under}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
