"""What a stacked row's device shape costs: the word axis flat
(``u32[S, R, 32768]``, the row axis tiled with it) against the word tile
(``u32[S, R, 256, 128]``, rows and shards untiled major dimensions).

Three programs, one JSON line (PERF.md §6, PR 32; the third PR 36):

  (a) a traced-index row take out of ``u32[256, 16, W]``, alone and
      feeding the popcount-sum of ``u32[256, 64, W] & row`` (TopN's shape);
  (b) SSB Q1's body — two ``range_between_dyn``, one row, ``sum_counts``
      over 27 bit planes — at 176 and 64 stacked shards.

  (c) the TopN walk's block pass (``nodes.topn_walk``, tile shape
      only): one block of K = 4, 8, 16 rows out of ``u32[S, 64, W]`` at
      S = 256 and 240 — ms and GB/s — and the whole walk over a field
      whose rows 24..63 are empty (it stops after 24 rows, 32 at
      K = 16) against the full pass over all 64; and over fields of 8
      and 64 dense near-equal rows, where it stops nowhere.

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
from pilosa_tpu.parallel import nodes                         # noqa: E402

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


def bench_walk(shards, rows=64, live=24, ks=(4, 8, 16), n=10):
    """(c): the block pass and the walk, through the program's own body
    (``nodes.topn_walk`` under a ``shard_map`` over one device, as
    ``wholequery._compile`` calls it)."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(jax.devices()[:1], ("shards",))
    stack_ = stack(7, shards, rows, WORD_TILE)
    stack_ = stack_.at[:, live:].set(0)
    masks = stack(8, shards, 1, WORD_TILE)
    ns = jnp.full((1,), n, jnp.int32)
    row_bytes = shards * SHARD_WORDS * 4

    def sharded(fn, n_rep):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P("shards"),) * 2 + (P(),) * n_rep,
            out_specs=P()))

    totals_fn = sharded(lambda st, _m: nodes.row_totals(st, "shards"), 0)
    totals = totals_fn(stack_, masks)
    full = sharded(lambda st, m: jax.lax.psum(
        jax.vmap(nodes.masked_counts)(st, m).sum(axis=0), "shards"), 0)
    full_s = timed(full, stack_, masks)
    want = jax.device_get(full(stack_, masks))
    res = {"rows": rows, "full_ms": 1e3 * full_s,
           "full_gbs": rows * row_bytes / full_s / 1e9,
           "totals_ms": 1e3 * timed(totals_fn, stack_, masks)}
    block_rows = nodes.TOPN_BLOCK_ROWS
    try:
        for k in ks:
            nodes.TOPN_BLOCK_ROWS = k

            def one(st, m, start, _k=k):
                blk = jax.lax.dynamic_slice_in_dim(st, start, _k, axis=1)
                return jax.lax.psum(
                    nodes.block_counts(blk, m[:, 0]), "shards")

            block_s = timed(sharded(one, 1), stack_, masks, jnp.int32(k))
            # (a fresh function a K: jit's cache does not see the global)
            starts, bounds = jax.jit(
                lambda t: nodes.walk_order(t))(totals)
            walk = sharded(lambda st, m, ns_, s_, b_: nodes.topn_walk(
                st, [m[:, 0]], ns_, s_, b_, "shards"), 3)
            walk_s = timed(walk, stack_, masks, ns, starts, bounds)
            counts, visited = jax.device_get(
                walk(stack_, masks, ns, starts, bounds))
            compiled = walk.lower(stack_, masks, ns, starts,
                                  bounds).compile()
            res[f"k{k}"] = {
                "block_ms": 1e3 * block_s,
                "block_gbs": k * row_bytes / block_s / 1e9,
                "walk_ms": 1e3 * walk_s, "rows_visited": int(visited),
                "walk_over_full": walk_s / full_s,
                "loop_overhead_ms": 1e3 * (
                    walk_s - int(visited) // k * block_s),
                "exact": bool((counts == want).all()),
                "while_ops": compiled.as_text().count(" while("),
                # under a block's bytes: the slice is read in place,
                # not copied out before the reduce
                "temp_bytes": compiled.memory_analysis()
                .temp_size_in_bytes}
    finally:
        nodes.TOPN_BLOCK_ROWS = block_rows
    return res


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
    out["walk"] = {f"s{s}": bench_walk(s)
                   for s in ((4, 3) if a.rehearsal else (256, 240))}
    # a field that stops nowhere (every row dense and near-equal): the
    # eight rows of a year field, and 64 such rows
    s_dense = 4 if a.rehearsal else 256
    out["walk_dense"] = {
        f"r{r}": bench_walk(s_dense, rows=r, live=r, ks=(8,))
        for r in (8, 64)}
    out["gain"] = {
        f"{prog}.{k}": round(out["flat"][prog][k] / out["tile"][prog][k], 3)
        for prog in out["tile"] for k in out["tile"][prog]
        if k.endswith("_ms")}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
