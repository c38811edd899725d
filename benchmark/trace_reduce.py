"""From a profiler trace to device busy time, per-operation totals and
idle gaps.  Two halves: ``read_events`` turns an ``.xplane.pb`` into
plain ``(plane, line, name, start_ns, dur_ns)`` tuples (the only part
that needs jax), and the arithmetic below works on such tuples alone, so
it is tested on hand-made intervals.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:"
# the lines of a device plane that hold one event per executed operation
# (the asynchronous ones, copies, on a line of their own); the other lines
# (modules, steps, annotations) nest or repeat them
OPS_LINES = ("XLA Ops", "Async XLA Ops")


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def read_events(path: str) -> list:
    """Every event of every plane: (plane, line, name, start_ns, dur_ns)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                out.append((plane.name, line.name, e.name,
                            float(e.start_ns), float(e.duration_ns)))
    return out


def short_name(name: str) -> str:
    """The trace names an operation by its whole HLO line: keep the
    operation's own name and the shape it produces."""
    head, sep, rest = name.partition(" = ")
    return f"{head} {rest.split(' ', 1)[0].rstrip(',')}"[:120] if sep \
        else name[:120]


def device_ops(events: list) -> dict:
    """{device plane: [(name, start_ns, dur_ns)]} of the operations that
    ran on each device: the ``XLA Ops`` lines where a plane has one, every
    line of the plane otherwise."""
    planes: dict = {}
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PLANE_PREFIX):
            planes.setdefault(plane, {}).setdefault(line, []).append(
                (short_name(name), start, dur))
    out = {}
    for plane, lines in planes.items():
        ops = [e for l in OPS_LINES for e in lines.get(l, [])]
        out[plane] = ops or [e for evs in lines.values() for e in evs]
    return {p: evs for p, evs in out.items() if evs}


def merge(intervals: list) -> list:
    """Sorted, disjoint [(start, end)] covering the same points as
    ``intervals`` [(start, end)]: overlapping and nested ones fused."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def busy_ns(ops: list) -> float:
    """Length of the union of the operations' intervals."""
    return sum(e - s for s, e in merge(
        [(start, start + dur) for _, start, dur in ops]))


def op_totals(ops: list) -> list:
    """[(name, total ns)] by total time, largest first."""
    totals: dict = {}
    for name, _, dur in ops:
        totals[name] = totals.get(name, 0.0) + dur
    return sorted(totals.items(), key=lambda kv: (-kv[1], kv[0]))


def gaps(ops: list) -> list:
    """[(name, start_ns, dur_ns)] of the idle gaps between the first and
    the last operation, named ``<op before> -> <op after>``."""
    evs = sorted(ops, key=lambda e: e[1])
    out = []
    reach, before = None, None
    for name, start, dur in evs:
        if reach is not None and start > reach:
            out.append((f"{before} -> {name}", reach, start - reach))
        if reach is None or start + dur > reach:
            reach, before = start + dur, name
    return out


def reduce(events: list, window_s: float) -> dict | None:
    """What the per-layer readers and ``breakdown`` take from a trace:
    busy seconds averaged over the devices that ran anything, the idle
    share of ``window_s``, and the ten largest operations and gaps.
    None where no operation ran on a device."""
    planes = device_ops(events)
    if not planes:
        return None
    busy = [busy_ns(ops) / 1e9 for ops in planes.values()]
    busy_s = sum(busy) / len(busy)
    all_ops = [e for ops in planes.values() for e in ops]
    n = len(planes)
    gap_list: list = []
    for ops in planes.values():
        gap_list.extend(gaps(ops))
    return {
        "devices": n,
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": [[name, ns / 1e9 / n]
                       for name, ns in op_totals(all_ops)[:10]],
        "idle_gaps": [[name, ns / 1e9 / n]
                      for name, ns in op_totals(gap_list)[:10]],
        "op_count": len(all_ops),
    }
