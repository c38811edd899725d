"""``Sum(filter, field=F)``: the sum of int field F over the columns
under the filter, with their count."""

from ops import filters


def render(t: dict, params: dict, cfg: dict) -> str:
    return f"Sum({filters.pql(t['filter'], params)}, field={t['field']})"


def expected(t: dict, params: dict, cube):
    preds = filters.ranges(t["filter"], params)
    return {"value": int(cube.select(preds, "total").sum()),
            "count": int(cube.select(preds, "count").sum())}


def rows_read(t: dict, params: dict, cfg: dict) -> list:
    """Every BSI row of the summed field, and the filter's rows."""
    return [(t["field"], None)] + filters.rows_read(t["filter"], params, cfg)
