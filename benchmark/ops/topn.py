"""``TopN(field, filter, n=N)``: the N rows of ``field`` with the most
columns under the filter, ties by row id ascending, empty rows left out."""

import numpy as np

from ops import filters


def render(t: dict, params: dict, cfg: dict) -> str:
    return (f"TopN({t['field']}, {filters.pql(t['filter'], params)}, "
            f"n={t['n']})")


def expected(t: dict, params: dict, cube):
    ax, _ = cube.field_axis(t["field"])
    sub = cube.select(filters.ranges(t["filter"], params))
    counts = sub.sum(axis=tuple(a for a in range(sub.ndim) if a != ax))
    order = np.lexsort((np.arange(counts.size), -counts))[:t["n"]]
    return [{"id": int(r), "count": int(counts[r])}
            for r in order if counts[r] > 0]


def rows_read(t: dict, params: dict, cfg: dict) -> list:
    """Every row of the TopN field, and each filter row."""
    return [(t["field"], None)] + filters.rows_read(t["filter"], params, cfg)
