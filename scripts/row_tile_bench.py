"""What a stacked row's device shape costs: the word axis flat
(``u32[S, R, 32768]``, the row axis tiled with it) against the word tile
(``u32[S, R, 256, 128]``, rows and shards untiled major dimensions).

Two programs, both shapes, one JSON line (PERF.md §6, PR 32):

  (a) a traced-index row take out of ``u32[256, 16, W]``, alone and
      feeding the popcount-sum of ``u32[256, 64, W] & row`` (TopN's shape);
  (b) SSB Q1's body — two ``range_between_dyn``, one row, ``sum_counts``
      over 27 bit planes — at 176 and 64 stacked shards.

    chiprun -- python scripts/row_tile_bench.py

A time from a CPU run of this script is not a speed of the system
(``--rehearsal`` shrinks the shard counts so the CPU finishes).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from pilosa_tpu.core import SHARD_WORDS, WORD_TILE            # noqa: E402
from pilosa_tpu.ops import bsi                                # noqa: E402

SHAPES = {"flat": (SHARD_WORDS,), "tile": WORD_TILE}


def stack(key, s, r, words):
    return jax.random.bits(jax.random.PRNGKey(key), (s, r) + words,
                           jnp.uint32)


def popsum(x, words):
    """popcount summed over the word axes, int32."""
    axes = tuple(range(x.ndim - len(words), x.ndim))
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=axes)


def take(frag, rid):
    return jax.lax.dynamic_index_in_dim(frag, rid, axis=0, keepdims=False)


def timed(fn, *args, batches=5, calls=20):
    """Median over ``batches`` of the mean seconds of ``calls`` enqueued
    back to back and waited for once (the launch cost overlaps)."""
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(batches):
        t0 = time.perf_counter()
        for _ in range(calls):
            r = fn(*args)
        jax.block_until_ready(r)
        out.append((time.perf_counter() - t0) / calls)
    return sorted(out)[len(out) // 2]


def one_sublane_ops(fn, *args) -> int:
    """Ops of the compiled program whose shape is tiled T(1,128)."""
    return fn.lower(*args).compile().as_text().count("T(1,128)")


def bench_take(words, shards):
    small = stack(1, shards, 16, words)
    rid = jnp.int32(5)
    alone = jax.jit(lambda st, r: jax.vmap(lambda f: take(f, r))(st))
    res = {"take_ms": 1e3 * timed(alone, small, rid),
           "take_t1_ops": one_sublane_ops(alone, small, rid)}
    big = stack(2, shards, 64, words)

    def topn(big_, st, r):
        def per_shard(b, f):
            return popsum(b & take(f, r)[None], words)
        return jnp.sum(jax.vmap(per_shard)(big_, st), axis=0)

    fed = jax.jit(topn)
    res["take_feeding_ms"] = 1e3 * timed(fed, big, small, rid)
    res["take_feeding_t1_ops"] = one_sublane_ops(fed, big, small, rid)
    return res


def sum_counts(frag, filt, words):
    """bsi.sum_counts with the word axes named (the flat form is the
    parent's kernel, the tiled one the change's)."""
    pos = frag[bsi.EXISTS_ROW] & filt & ~frag[bsi.SIGN_ROW]
    neg = frag[bsi.EXISTS_ROW] & filt & frag[bsi.SIGN_ROW]
    planes = frag[bsi.OFFSET_ROW:]
    return jnp.stack([
        jnp.concatenate([popsum(planes & pos[None], words),
                         popsum(pos, words)[None]]),
        jnp.concatenate([popsum(planes & neg[None], words),
                         popsum(neg, words)[None]])])


def bench_q1(words, shards):
    year = stack(3, shards, 8, words)
    qty = stack(4, shards, 8, words)       # depth 6
    disc = stack(5, shards, 8, words)      # depth 4 + 2 unused planes
    ext = stack(6, shards, 29, words)      # depth 27
    bits = jnp.asarray([[1, 0, 1] + [0] * 60, [1, 1, 0] + [0] * 60],
                       jnp.int32)
    rid = jnp.int32(3)

    def q1(year_, qty_, disc_, ext_, bits_, r):
        def per_shard(y, q, d, e):
            filt = take(y, r) \
                & bsi.range_between_dyn(d, "pos", bits_[0], "pos", bits_[1]) \
                & bsi.range_between_dyn(q, "pos", bits_[0], "pos", bits_[1])
            return sum_counts(e, filt, words)
        return jnp.sum(jax.vmap(per_shard)(year_, qty_, disc_, ext_), axis=0)

    fn = jax.jit(q1)
    args = (year, qty, disc, ext, bits, rid)
    return {"q1_ms": 1e3 * timed(fn, *args),
            "q1_t1_ops": one_sublane_ops(fn, *args)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny shard counts: a CPU run that only shows the "
                         "script runs")
    a = ap.parse_args()
    dev = jax.devices()[0]
    s_take, s_q1 = (4, (3, 2)) if a.rehearsal else (256, (176, 64))
    out = {"device": dev.platform, "device_kind": dev.device_kind,
           "rehearsal": a.rehearsal}
    for name, words in SHAPES.items():
        out[name] = {"a": bench_take(words, s_take)}
        for s in s_q1:
            out[name][f"b{s}"] = bench_q1(words, s)
    out["gain"] = {
        f"{prog}.{k}": round(out["flat"][prog][k] / out["tile"][prog][k], 3)
        for prog in out["tile"] for k in out["tile"][prog]
        if k.endswith("_ms")}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
