"""The one generator: a configuration's columns, shard by shard, from a seed.

A configuration file (``configs/<name>.json``) lists ``columns`` — each
either drawn (``weights``, ``uniform``, ``geometric``) or ``derived`` from
other columns by a function under ``generators/`` — and ``fields``, each
naming the column it stores.  One rng per (seed, shard), so loader threads
and the reference agree without sharing a stream.  Plain numpy; nothing
here imports the program.
"""

from __future__ import annotations

import importlib
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SHARD_WIDTH = 1 << 20


def load_json(kind: str, name: str) -> dict:
    """``benchmark/<kind>/<name>.json`` — how every data file is found
    by the name ``BENCHMARK.json`` gives."""
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def generator_fn(spec: str):
    """``"module.function"`` or ``"module"`` (function ``generate``) under
    ``generators/``."""
    mod, _, fn = spec.partition(".")
    module = importlib.import_module(f"generators.{mod}")
    return getattr(module, fn or "generate")


def draw_cdf(draw: dict) -> tuple[int, np.ndarray | None]:
    """(lowest value, cumulative weights or None for uniform) of a draw."""
    kind = draw["kind"]
    if kind == "uniform":
        return int(draw.get("lo", 0)), None
    if kind == "weights":
        w = np.asarray(draw["weights"], dtype=np.float64)
    elif kind == "geometric":
        w = float(draw["ratio"]) ** np.arange(int(draw["n"]))
    else:
        raise ValueError(f"unknown draw kind {kind!r}")
    cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0
    return int(draw.get("lo", 0)), cdf


def draw_size(draw: dict) -> int:
    """How many distinct values a draw has."""
    return len(draw["weights"]) if draw["kind"] == "weights" \
        else int(draw["n"])


def draw_weights(draw: dict) -> np.ndarray:
    """Probability of each value, lowest first."""
    cdf = draw_cdf(draw)[1]
    if cdf is None:
        n = draw_size(draw)
        return np.full(n, 1.0 / n)
    return np.diff(cdf, prepend=0.0)


def draw_values(rng: np.random.Generator, draw: dict, size: int) -> np.ndarray:
    lo, cdf = draw_cdf(draw)
    if cdf is None:
        return rng.integers(lo, lo + int(draw["n"]), size=size,
                            dtype=np.int32)
    out = np.searchsorted(cdf, rng.random(size),
                          side="right").astype(np.int32)
    np.minimum(out, cdf.size - 1, out=out)
    return out + lo if lo else out


def column_spec(cfg: dict, name: str) -> dict:
    for c in cfg["columns"]:
        if c["name"] == name:
            return c
    raise KeyError(f"configuration {cfg['name']} has no column {name!r}")


def derive(cfg: dict, name: str, cols: dict) -> np.ndarray:
    d = column_spec(cfg, name)["derived"]
    return generator_fn(d["generator"])(*(cols[a] for a in d["args"]))


def shard_columns(cfg: dict, seed: int, shard: int,
                  width: int = SHARD_WIDTH) -> dict:
    """{column name: values of the shard's ``width`` rows}.  Drawn columns
    come in the file's order from one rng; derived ones follow."""
    rng = np.random.default_rng([int(seed), int(shard)])
    cols: dict = {}
    for c in cfg["columns"]:
        if "draw" in c:
            cols[c["name"]] = draw_values(rng, c["draw"], width)
    for c in cfg["columns"]:
        if "derived" in c:
            cols[c["name"]] = derive(cfg, c["name"], cols)
    return cols
