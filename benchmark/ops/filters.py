"""What the ops share: a template's filter — a conjunction of ``eq`` /
``between`` / ``lt`` predicates with ``$PARAM`` or ``$PARAM+k`` values —
rendered as PQL, as inclusive ranges for the cube, and as the rows it
makes a query read."""

from __future__ import annotations

import re

_VALUE = re.compile(r"^\$([A-Za-z_]\w*)(?:\+(\d+))?$")
_LOWEST = -(1 << 62)


def value(v, params: dict) -> int:
    if isinstance(v, int):
        return v
    m = _VALUE.match(v)
    if not m:
        raise ValueError(f"bad template value {v!r}")
    return int(params[m.group(1)]) + int(m.group(2) or 0)


def field_spec(cfg: dict, name: str) -> dict:
    return next(f for f in cfg["fields"] if f["name"] == name)


def pql(filt: list, params: dict) -> str:
    """The filter as one PQL bitmap call."""
    rows = []
    for p in filt:
        if "eq" in p:
            rows.append(f"Row({p['eq']}={value(p['value'], params)})")
        elif "between" in p:
            rows.append(f"Row({p['between']} >< [{value(p['lo'], params)}, "
                        f"{value(p['hi'], params)}])")
        elif "lt" in p:
            rows.append(f"Row({p['lt']} < {value(p['value'], params)})")
        else:
            raise ValueError(f"unknown predicate {p!r}")
    return rows[0] if len(rows) == 1 else f"Intersect({', '.join(rows)})"


def ranges(filt: list, params: dict) -> list:
    """[(field, lo, hi)] inclusive, for ``Cube.masks``."""
    out = []
    for p in filt:
        if "eq" in p:
            v = value(p["value"], params)
            out.append((p["eq"], v, v))
        elif "between" in p:
            out.append((p["between"], value(p["lo"], params),
                        value(p["hi"], params)))
        else:
            out.append((p["lt"], _LOWEST, value(p["value"], params) - 1))
    return out


def rows_read(filt: list, params: dict, cfg: dict) -> list:
    """[(field, row ids or None for every row)] a filter must read: the
    one row of an ``eq`` on a set field, every BSI row (exists and sign
    among them) of a range over an int field."""
    out = []
    for p in filt:
        name = p.get("eq") or p.get("between") or p.get("lt")
        if field_spec(cfg, name)["type"] == "int":
            out.append((name, None))
        else:
            out.append((name, [value(p["value"], params)]))
    return out
