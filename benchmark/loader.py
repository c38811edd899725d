"""Columns -> per-shard bit rows -> the server's fragments.

Set-up, not traffic: the window's requests go over HTTP, but loading a
deployment through the HTTP import routes costs minutes (PERF.md), and
every run of every later check would pay it.  So the schema is created
over HTTP and the fragments are filled in-process, one write and one
snapshot per fragment, from a thread pool; each fragment leaves the
snapshot file a restarted server would open.
"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import datagen
import roofline

SHARD_WORDS = datagen.SHARD_WIDTH // 32
LOADERS = 8
# BSI fragment rows, as pilosa_tpu/ops/bsi.py names them
EXISTS_ROW, SIGN_ROW, OFFSET_ROW = 0, 1, 2


def pack_bits(mask: np.ndarray) -> np.ndarray:
    """bool[2^20] -> uint32[32768], column c at word c >> 5, bit c & 31."""
    return np.packbits(mask, bitorder="little").view(np.uint32)


def bit_depth(v: int) -> int:
    return max(int(v).bit_length(), 1)


def field_depth(field: dict) -> int:
    """Bit depth of an int field: of the widest ``value - base``."""
    base = field_base(field)
    return max(bit_depth(field["max"] - base), bit_depth(field["min"] - base))


def field_base(field: dict) -> int:
    """An int field's base as the program defaults it (field.go bsiBase)."""
    lo, hi = field["min"], field["max"]
    return lo if lo > 0 else hi if hi < 0 else 0


def field_rows(field: dict) -> int:
    """Rows a field's fragment holds, in row-id order."""
    if field["type"] == "int":
        return OFFSET_ROW + field_depth(field)
    return int(field["rows"])


def field_block(field: dict, values: np.ndarray) -> np.ndarray:
    """The dense ``uint32[rows, 32768]`` block of one field in one shard
    (every column of the shard holds a value)."""
    n = field_rows(field)
    out = np.zeros((n, SHARD_WORDS), dtype=np.uint32)
    if field["type"] == "int":
        v = values.astype(np.int64) - field_base(field)
        if v.min() < 0:
            raise ValueError(f"{field['name']}: value below the base")
        out[EXISTS_ROW] = 0xFFFFFFFF
        for i in range(n - OFFSET_ROW):
            out[OFFSET_ROW + i] = pack_bits((v >> i) & 1 > 0)
    else:
        for r in range(n):
            out[r] = pack_bits(values == r)
    return out


def fill_fragment(frag, block: np.ndarray):
    """One write and one snapshot: the block's non-zero words become the
    fragment's sparse store (row r of ``block`` is row id r)."""
    flat = block.reshape(-1)
    nz = np.flatnonzero(flat)
    with frag._lock:
        frag._ensure_rows(block.shape[0] - 1)
        frag._or_words(nz.astype(np.int64), flat[nz])
        frag._rank_invalidate()
        frag._mark_device_dirty()
        frag.snapshot()


def create_schema(client, cfg: dict):
    index = cfg["index"]["name"]
    client.request("POST", f"/index/{index}",
                   {"options": cfg["index"]["options"]})
    for f in cfg["fields"]:
        opts = {"type": "int", "min": f["min"], "max": f["max"]} \
            if f["type"] == "int" else {}
        client.request("POST", f"/index/{index}/field/{f['name']}",
                       {"options": opts})


def load(holder, cfg: dict, seed: int, shards: int, cube=None) -> dict:
    """Generate, fill and account every shard.  ``cube`` (an
    ``oracle.Cube``) is fed each shard's columns.  Returns {field: (set
    bits, least bytes) of each row, summed over the shards}: the load
    check's marginals and the roofline's bytes."""
    index = holder.index(cfg["index"]["name"])
    views = {}
    for f in cfg["fields"]:
        field = index.field(f["name"])
        if f["type"] == "int":
            # what Field.import_values does before it writes: the depth
            # is schema, and a restarted server reads it from the meta
            field.options.bit_depth = field_depth(f)
            field.save_meta()
            views[f["name"]] = field._create_view_if_not_exists(
                field.bsi_view_name())
        else:
            views[f["name"]] = field._create_view_if_not_exists("standard")
    exists = index.existence_field()
    exists_view = exists._create_view_if_not_exists("standard") \
        if exists is not None else None
    from pilosa_tpu.core import bump_schema_epoch
    bump_schema_epoch()
    row_stats: dict = {}
    lock = threading.Lock()
    all_ones = np.full((1, SHARD_WORDS), 0xFFFFFFFF, dtype=np.uint32)

    def one(shard: int):
        cols = datagen.shard_columns(cfg, seed, shard)
        mine = {}
        for f in cfg["fields"]:
            block = field_block(f, cols[f["column"]])
            fill_fragment(
                views[f["name"]].create_fragment_if_not_exists(shard), block)
            mine[f["name"]] = np.stack(roofline.row_stats(block))
        if exists_view is not None:
            fill_fragment(
                exists_view.create_fragment_if_not_exists(shard), all_ones)
        cells = cube.shard_cells(cols) if cube is not None else None
        with lock:
            for name, b in mine.items():
                row_stats[name] = row_stats.get(name, 0) + b
            if cells is not None:
                cube.add(cells, last=shard == shards - 1)

    with ThreadPoolExecutor(LOADERS) as pool:
        for _ in pool.map(one, range(shards)):
            pass
    return {name: (s[0], s[1]) for name, s in row_stats.items()}
