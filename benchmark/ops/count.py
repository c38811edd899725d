"""``Count(filter)``: the columns under the filter."""

from ops import filters


def render(t: dict, params: dict, cfg: dict) -> str:
    return f"Count({filters.pql(t['filter'], params)})"


def expected(t: dict, params: dict, cube):
    return int(cube.select(filters.ranges(t["filter"], params)).sum())


def rows_read(t: dict, params: dict, cfg: dict) -> list:
    return filters.rows_read(t["filter"], params, cfg)
