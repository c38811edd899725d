"""Compiles inside the measured window: the executables jax itself
reported building (or loading from its persistent cache) in the server's
process between the window's start and its end — eager ones, which the
program's counters do not see, among them — or the program's own count
over the window (``vars_ratio`` on the same spec) where that is more."""

from readers import vars_ratio


def read(spec: dict, ctx: dict):
    seen = ctx.get("executables_built_in_window")
    counted = vars_ratio.read(spec, ctx) if "num" in spec else None
    values = [v for v in (seen, counted) if v is not None]
    return max(values) if values else None
