"""The least bytes a query must read, from the generated data alone.

For every (operand row, shard, 2^16-column container) the smallest of
roaring's three forms: 8192 bytes as a bitmap, 2 bytes a set bit as an
array, 4 bytes a run as runs.  No kernel over any form the program has
can read less, so bytes / (peak bandwidth x device busy time) is a share
of the roofline that cannot pass 100 % — as long as a query's operands
are read once per query.  A program that shares operand reads across the
queries of one launch can pass it; the count then has to be corrected by
a benchmark PR before that change is judged.
"""

from __future__ import annotations

import json
import os

import numpy as np

CONTAINER_WORDS = 2048          # 2^16 columns of uint32 words
BITMAP_BYTES = 8192


def container_stats(rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(cardinality, runs) per container of ``rows`` — uint32
    ``[n_rows, words]`` with ``words`` a multiple of 2048 — each
    ``[n_rows, words / 2048]``.  A run that crosses a container's edge
    counts in both."""
    n, words = rows.shape
    c = rows.reshape(n, words // CONTAINER_WORDS, CONTAINER_WORDS)
    card = np.bitwise_count(c).sum(axis=2, dtype=np.int64)
    # a run starts at a set bit whose lower neighbour is clear; the
    # neighbour of bit 0 is the word before's bit 31, clear at an edge
    carry = np.zeros_like(c)
    carry[:, :, 1:] = c[:, :, :-1] >> np.uint32(31)
    starts = c & ~((c << np.uint32(1)) | carry)
    runs = np.bitwise_count(starts).sum(axis=2, dtype=np.int64)
    return card, runs


def row_stats(rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(set bits, least bytes to read) of each row of ``rows``
    (``[n_rows, words]``): per container min(8192, 2 x cardinality,
    4 x runs), summed over the row's containers."""
    card, runs = container_stats(rows)
    per = np.minimum(BITMAP_BYTES, np.minimum(2 * card, 4 * runs))
    return card.sum(axis=1), per.sum(axis=1)


def least_bytes(rows: np.ndarray) -> np.ndarray:
    return row_stats(rows)[1]


def peak(device_kind: str) -> dict:
    """The table of peaks, keyed by ``device_kind``; an unknown kind is
    an error, not a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json")
    return peaks[device_kind]
