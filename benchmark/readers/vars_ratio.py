"""A ratio of ``/debug/vars`` deltas over a span.  ``num`` and ``den`` are
dotted paths into the snapshot (a list of paths is summed), ``"n"`` (the
requests completed in the span) or a number; the result is scaled by
``scale``.  ``span`` is ``trace`` (the traced seconds) or ``window`` (the
whole measured window).  Nothing to read — a path absent, a zero
denominator — returns nothing."""

from __future__ import annotations


def _path(snapshot: dict, dotted: str):
    """Dotted path, where a key may itself hold dots (``http.query``)."""
    node, rest = snapshot, dotted
    while rest:
        if not isinstance(node, dict):
            return None
        for cut in range(len(rest), 0, -1):
            if (cut == len(rest) or rest[cut] == ".") and rest[:cut] in node:
                node, rest = node[rest[:cut]], rest[cut + 1:]
                break
        else:
            return None
    return node


def _term(term, before: dict, after: dict, n: int):
    if term == "n":
        return n
    if isinstance(term, (int, float)):
        return term
    total = 0.0
    for p in term if isinstance(term, list) else [term]:
        a, b = _path(after, p), _path(before, p)
        if not isinstance(a, (int, float)) or not isinstance(b, (int, float)):
            return None
        total += a - b
    return total


def read(spec: dict, ctx: dict):
    span = ctx["spans"].get(spec.get("span", "trace"))
    if span is None:
        return None
    num = _term(spec["num"], span["before"], span["after"], span["n"])
    den = _term(spec["den"], span["before"], span["after"], span["n"])
    if num is None or not den:
        return None
    return spec.get("scale", 1) * num / den
