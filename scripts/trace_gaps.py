#!/usr/bin/env python3
"""Put a profiler trace's device idle gaps down to what the dispatcher
thread and the request threads were doing.

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

Beside it, how many request threads were where: a request thread's
line (one that holds an ``http.query``) is inside ``query.wait``,
``query.fetch``, ``query.plan``, a launch it makes itself
(``dispatch.place`` / ``dispatch.enqueue``, where the batcher does not
take the kind), elsewhere in ``http.query`` (its own front end and the
rest of ``query.exec``) or outside any request.  A gap's thread-seconds
in a state over its length is the mean number of request threads in
that state while the device stood.  The dispatcher's spans are those of
the line that holds its partition, so a request thread's own launch is
never given to it.

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
# the request thread's spans (docs/observability.md "Layer spans")
REQUEST_SPANS = ("http.query", "query.plan", "query.wait", "query.fetch",
                 "dispatch.place", "dispatch.enqueue")
PARTITION = ("dispatch.idle", "dispatch.window", "dispatch.round")
QUERY_REST = "http.query (rest)"    # http.query.self and query.exec
OUTSIDE = "(outside http.query)"


def host_spans(lines: dict) -> list:
    """[(name, start_ns, end_ns)] of the dispatcher's layer spans: the
    ``dispatch.*`` events of the ``host_lines`` line(s) that hold its
    partition."""
    return [e for events in lines.values()
            if any(name in PARTITION for name, _, _ in events)
            for e in events if e[0].startswith(SPAN_PREFIX)]


def request_lines(lines: dict) -> dict:
    """{line: [(name, start_ns, end_ns)]} of the request spans on each
    of ``host_lines``' lines that holds an ``http.query``."""
    out = {}
    for line, events in lines.items():
        spans = [e for e in events if e[0] in REQUEST_SPANS]
        if any(name == "http.query" for name, _, _ in spans):
            out[line] = spans
    return out


def flatten(spans: list, rest: dict | None = None) -> list:
    """Disjoint, sorted [(name, start, end)] covering the same points as
    the nested ``spans``, each stretch under its innermost span's name;
    the part of a span outside its children is named by ``rest``
    (default: ``dispatch.round``'s is ROUND_REST)."""
    rest = {"dispatch.round": ROUND_REST} if rest is None else rest
    out: list = []
    stack: list = []          # (name, end) of the open spans, outermost first
    cursor = None

    def emit(upto):
        nonlocal cursor
        if upto > cursor:
            name = stack[-1][0]
            out.append((rest.get(name, name), cursor, upto))
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


def threads_in(gap_list: list, lines: dict):
    """Where the request threads were during each gap: ``lines`` maps a
    thread's line to its ``flatten``-ed request spans.  Returns
    ({state: thread-ns}, [{state: threads}] one a gap), a gap's
    thread-ns in a state over its length being the mean number of
    threads in it; OUTSIDE is a thread in no ``http.query``."""
    totals: dict = {}
    per_gap: list = [{} for _ in gap_list]
    for segments in lines.values():
        _, shares = attribute(gap_list, segments)
        for acc, (_, gdur, sh) in zip(per_gap, shares):
            for state, ns in sh.items():
                state = OUTSIDE if state == UNNAMED else state
                totals[state] = totals.get(state, 0.0) + ns
                acc[state] = acc.get(state, 0.0) + ns / gdur
    return totals, per_gap


def host_lines(path: str) -> dict:
    """{(host line index, line name): [(name, start_ns, end_ns)]}, one
    entry a thread: Python threads share a line name, so only the index
    tells their lines apart."""
    from jax.profiler import ProfileData
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            out[(i, line.name)] = [
                (e.name, float(e.start_ns),
                 float(e.start_ns) + float(e.duration_ns))
                for e in line.events]
    return out


def report(path: str) -> dict:
    events = trace_reduce.read_events(path)
    host = host_lines(path)
    spans = host_spans(host)
    segments = flatten(spans)
    gap_list = [g for ops in trace_reduce.device_ops(events).values()
                for g in trace_reduce.gaps(ops)]
    totals, per_gap = attribute(gap_list, segments)
    lines = {line: flatten(spans, {"http.query": QUERY_REST})
             for line, spans in request_lines(host).items()}
    req_totals, req_per_gap = threads_in(gap_list, lines)
    idle_ns = sum(g[2] for g in gap_list)
    named = idle_ns - totals.get(UNNAMED, 0.0)
    order = sorted(range(len(per_gap)), key=lambda i: -per_gap[i][1])[:10]
    return {
        "file": path,
        "lines": {line: n for line, evs in host.items()
                  if (n := sum(1 for e in evs
                               if e[0].startswith(SPAN_PREFIX)))},
        "partition_overlap_s": overlap_ns(spans, PARTITION) / 1e9,
        "idle_s": idle_ns / 1e9,
        "named_share": named / idle_ns if idle_ns else None,
        "by_span": sorted(((n, ns / 1e9) for n, ns in totals.items()),
                          key=lambda kv: -kv[1]),
        "request_threads": len(lines),
        # mean request threads in each state over all the idle
        "threads_by_state": sorted(
            ((n, ns / idle_ns) for n, ns in req_totals.items()),
            key=lambda kv: -kv[1]) if idle_ns else [],
        "longest": [(per_gap[i][0], per_gap[i][1] / 1e9,
                     sorted(((s, ns / 1e9)
                             for s, ns in per_gap[i][2].items()),
                            key=lambda kv: -kv[1]),
                     sorted(req_per_gap[i].items(), key=lambda kv: -kv[1]))
                    for i in order],
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
        print(f"{r['request_threads']} request threads while the device "
              "stood, mean threads a state:")
        print("| state | threads |")
        print("|---|---|")
        for name, n in r["threads_by_state"]:
            print(f"| `{name}` | {n:.2f} |")
        print("ten longest gaps:")
        for name, dur, shares, threads in r["longest"]:
            under = ", ".join(f"{n} {1e3 * s:.2f} ms" for n, s in shares)
            where = ", ".join(f"{n} {k:.2f}" for n, k in threads)
            print(f"  {1e3 * dur:8.2f} ms  {name}  <- {under}"
                  + (f"; request threads: {where}" if where else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
