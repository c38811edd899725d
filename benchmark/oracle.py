"""The plain reference: one aggregate cube per (configuration, seed).

The cube counts the rows (and sums a measure) in every cell of the
combined ordinal key of a few base columns — the mix names them under
``cube.axes`` — built shard by shard while the data is generated.  Every
expected answer is then a slice-sum of the cube, so checking thousands of
answers costs milliseconds.  A field that is a function of one axis
column (``d_year`` of ``day``) selects cells through that function.
Plain numpy; nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

import datagen


class Cube:
    def __init__(self, cfg: dict, mix: dict):
        self.cfg = cfg
        spec = mix["cube"]
        self.axes = list(spec["axes"])
        self.measure = spec.get("measure")
        self.lo, self.shape = [], []
        for a in self.axes:
            draw = datagen.column_spec(cfg, a)["draw"]
            self.lo.append(int(draw.get("lo", 0)))
            self.shape.append(datagen.draw_size(draw))
        self.cells = int(np.prod(self.shape))
        self.count = np.zeros(self.cells, dtype=np.int64)
        self.total = np.zeros(self.cells, dtype=np.int64)
        # the control's view: the last shard's load never became visible
        self.stale_count = self.stale_total = None
        self._axis_values: dict = {}

    # -- building ------------------------------------------------------------

    def shard_cells(self, cols: dict) -> tuple:
        """(count, total) of one shard's rows per cell.  bincount's
        weights are float64: a shard's sum per cell stays far under 2^53,
        and cells are added as int64."""
        key = np.ravel_multi_index(
            [cols[a].astype(np.int64) - lo
             for a, lo in zip(self.axes, self.lo)], self.shape)
        count = np.bincount(key, minlength=self.cells)
        total = None
        if self.measure is not None:
            total = np.bincount(
                key, weights=cols[self.measure].astype(np.float64),
                minlength=self.cells).astype(np.int64)
        return count, total

    def add(self, cells: tuple, last: bool = False):
        count, total = cells
        if last:
            self.stale_count, self.stale_total = count, total
        self.count += count
        if total is not None:
            self.total += total

    def stale(self) -> "Cube":
        """The control: this cube as a reader would see it who missed the
        last shard's acknowledged load (a stale read, which the
        configuration's guarantees forbid)."""
        out = Cube.__new__(Cube)
        out.__dict__.update(self.__dict__)
        out.count = self.count - self.stale_count
        out.total = self.total - (self.stale_total
                                  if self.stale_total is not None else 0)
        return out

    # -- selecting -----------------------------------------------------------

    def field_axis(self, field: str) -> tuple[int, np.ndarray]:
        """(axis number, the field's value at each ordinal of that axis)."""
        if field not in self._axis_values:
            column = next(f["column"] for f in self.cfg["fields"]
                          if f["name"] == field)
            if column in self.axes:
                ax = self.axes.index(column)
                vals = self.lo[ax] + np.arange(self.shape[ax])
            else:
                args = datagen.column_spec(self.cfg, column)["derived"]["args"]
                on = [a for a in args if a in self.axes]
                if len(args) != 1 or not on:
                    raise ValueError(
                        f"field {field!r} is not a function of one axis")
                ax = self.axes.index(on[0])
                base = self.lo[ax] + np.arange(self.shape[ax])
                vals = datagen.derive(self.cfg, column, {on[0]: base})
            self._axis_values[field] = (ax, vals)
        return self._axis_values[field]

    def masks(self, preds: list) -> list:
        """One bool mask per axis from a conjunction of (field, lo, hi)
        inclusive ranges."""
        masks = [np.ones(n, dtype=bool) for n in self.shape]
        for field, lo, hi in preds:
            ax, vals = self.field_axis(field)
            masks[ax] &= (vals >= lo) & (vals <= hi)
        return masks

    def select(self, preds: list, what: str = "count") -> np.ndarray:
        """The selected sub-cube of ``count`` or ``total``."""
        arr = (self.count if what == "count" else self.total) \
            .reshape(self.shape)
        return arr[np.ix_(*self.masks(preds))]
