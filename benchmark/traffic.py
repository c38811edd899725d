"""The one traffic generator: a mix file's templates and parameter draws,
from a seed, into each client's sequence of request bodies with the
reference's answer to each.  A mix is data (``traffic/<mix>.json``); an op
it names is a file under ``ops/``.
"""

from __future__ import annotations

import importlib

import numpy as np

import datagen

REQUESTS_PER_CLIENT = 4096      # a client that runs out starts over


_OPS: dict = {}


def op_module(name: str):
    """``ops/<name>.py``, found by the name a template gives."""
    if name not in _OPS:
        _OPS[name] = importlib.import_module(f"ops.{name}")
    return _OPS[name]


def draw_param(rng, spec: dict, cfg: dict, size: int) -> np.ndarray:
    if spec["kind"] == "column":
        spec = datagen.column_spec(cfg, spec["column"])["draw"]
    return datagen.draw_values(rng, spec, size)


def template_order(rng, templates: list, size: int) -> np.ndarray:
    """Which template each of a client's requests uses: whole-number
    ``weight``s, dealt in shuffled blocks that each hold the mix exactly,
    so every seed sends the same work in another order and any stretch of
    a run holds the mix to within a block."""
    block = np.repeat(np.arange(len(templates)),
                      [int(t.get("weight", 1)) for t in templates])
    block = np.tile(block, max(1, 12 // block.size))
    blocks = [rng.permutation(block) for _ in range(-(-size // block.size))]
    return np.concatenate(blocks)[:size]


class Requests:
    """Distinct requests (``pql``, ``template``, ``params``) and each
    client's sequence of indices into them."""

    def __init__(self, cfg: dict, mix: dict, seed: int,
                 per_client: int = REQUESTS_PER_CLIENT):
        self.cfg, self.mix = cfg, mix
        self.pql: list = []
        self.template: list = []
        self.params: list = []
        self._index: dict = {}
        templates = mix["templates"]
        self.sequences = []
        for c in range(int(mix["clients"])):
            rng = np.random.default_rng([int(seed), 0x7AFF1C, c])
            which = template_order(rng, templates, per_client)
            drawn = [{k: draw_param(rng, spec, cfg, per_client)
                      for k, spec in t["params"].items()}
                     for t in templates]
            self.sequences.append([
                self.add(int(ti), {k: int(v[i])
                                   for k, v in drawn[ti].items()})
                for i, ti in enumerate(which)])

    def add(self, ti: int, params: dict) -> int:
        t = self.mix["templates"][ti]
        pql = op_module(t["op"]).render(t, params, self.cfg)
        i = self._index.get(pql)
        if i is None:
            i = self._index[pql] = len(self.pql)
            self.pql.append(pql)
            self.template.append(ti)
            self.params.append(params)
        return i

    def expected(self, i: int, cube):
        t = self.mix["templates"][self.template[i]]
        return op_module(t["op"]).expected(t, self.params[i], cube)

    def least_bytes(self, i: int, row_bytes: dict) -> int:
        """Least bytes request ``i`` must read (``roofline.py``), its
        operands counted once."""
        t = self.mix["templates"][self.template[i]]
        total = 0
        for field, rows in op_module(t["op"]).rows_read(
                t, self.params[i], self.cfg):
            b = row_bytes[field]
            total += int(b.sum() if rows is None else b[rows].sum())
        return total
