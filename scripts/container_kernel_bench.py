#!/usr/bin/env python3
"""Time the container kernel alone: ms a launch of the fare field's
array entries, 53 stacked shards, for each register-group count and
step width, with the lane gathers a step makes beside it.

The fare field is ``taxi-1b-chip1``'s ``total_amount_dollars`` (128
rows), each shard made by the benchmark's own generator and packed as
the server packs it.  Its array entries are laid out in ``8 * G`` class
streams (``containers.class_streams``): G = 4, the layout the server
keeps (a stream a register group of 8 registers and a sublane: 9 216
lanes a stream at the launch's bucket, ``containers.stream_bucket``);
G = 8 (groups of 4 registers: half the gathers, twice the rows a
chunk); and G = 1, the eight class streams over all 32 registers that
the layout replaced.  Each is counted by ``kernels._count_entries`` —
the kernel ``container_row_counts`` and the span reduction before it —
under ``jax.vmap`` over the stacked shards, as the call site maps it:
one ``pallas_call`` whose grid is (shard, group, block).  A step of
``lanes`` lanes makes lanes / 128 x 32 / G x params rows gathers.

Every form's counts are checked against XLA's gather form
(``backend="jnp"``).  One JSON line on stdout and in
``chiprun_out/container_kernel_bench.json``.  On the CPU the kernel is
interpreted: ``--shards 2 --iters 1`` rehearses the script, and its
times are not the kernel's.

    chiprun -- python scripts/container_kernel_bench.py
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

import numpy as np  # noqa: E402

FIELD = "total_amount_dollars"


def fare_packs(shards: int, seed: int):
    """The fare field's generated shards' array entries (ascending flat
    indices, values) as the server packs them, and the field's rows."""
    import datagen
    import loader
    from pilosa_tpu.ops.containers import ARRAY_PAD, pack_words
    cfg = datagen.load_json("configs", "taxi-1b-chip1")
    field = next(f for f in cfg["fields"] if f["name"] == FIELD)
    entries = []
    for shard in range(shards):
        block = loader.field_block(
            field, datagen.shard_columns(cfg, seed, shard)[FIELD])
        flat = block.reshape(-1)
        nz = np.flatnonzero(flat)
        p = pack_words(nz.astype(np.int64), flat[nz])
        live = p.a_idx != ARRAY_PAD
        order = np.argsort(p.a_idx[live])
        entries.append((p.a_idx[live][order], p.a_val[live][order]))
    return entries, loader.field_rows(field)


def stacked_streams(entries, groups: int):
    """(a_idx, a_val) ``[shards, 8 * groups, L]``: each shard's entries in
    ``8 * groups`` class streams, padded to the launch's bucket as
    ``MeshExecutor._place_packed_block`` pads them."""
    from pilosa_tpu.ops.containers import (
        ARRAY_PAD, class_streams, stream_bucket)
    streams = [class_streams(i, v, groups) for i, v in entries]
    lanes = stream_bucket(max(a.shape[1] for a, _ in streams))
    a_idx = np.full((len(streams), 8 * groups, lanes), ARRAY_PAD,
                    dtype=np.int32)
    a_val = np.zeros((len(streams), 8 * groups, lanes), dtype=np.uint32)
    for k, (i, v) in enumerate(streams):
        a_idx[k, :, : i.shape[1]] = i
        a_val[k, :, : v.shape[1]] = v
    return a_idx, a_val


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument("--shards", type=int, default=53)
    ap.add_argument("--seed", type=int, default=3800000038)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--params-rows", default="1,2,4")
    ap.add_argument("--groups", default="4,8,1")
    ap.add_argument("--lanes", default="512,768,1024,1152,1536,2048,2304,"
                                       "3072,4608")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from pilosa_tpu.core import SHARD_WORDS, WORD_TILE
    from pilosa_tpu.ops import kernels

    t0 = time.perf_counter()
    entries, rows = fare_packs(args.shards, args.seed)
    out = {"device": jax.devices()[0].device_kind, "shards": args.shards,
           "rows": rows, "entries": int(sum(i.size for i, _ in entries)),
           "interpreted": kernels.interpret_mode(),
           "generate_s": round(time.perf_counter() - t0, 2),
           "lanes_a_stream": {}, "ms_a_launch": {}}
    rng = np.random.default_rng(args.seed)
    for g in (int(x) for x in args.groups.split(",")):
        a_idx, a_val = stacked_streams(entries, g)
        L = a_idx.shape[2]
        out["lanes_a_stream"][f"g{g}"] = L
        idx, val = jnp.asarray(a_idx), jnp.asarray(a_val)
        for b in (int(x) for x in args.params_rows.split(",")):
            filts = jnp.asarray(rng.integers(
                0, 1 << 32, (args.shards, b) + WORD_TILE,
                dtype=np.uint64).astype(np.uint32))
            # XLA's gather and scatter-add: the counts every form must give
            flat = filts.reshape(args.shards, b, SHARD_WORDS)

            def xla(f, i, v, _b=b):
                i, v = i.reshape(-1), v.reshape(-1)
                ok = i < rows * SHARD_WORDS
                bits = jnp.where(ok, jax.lax.population_count(
                    v & f[:, jnp.where(ok, i % SHARD_WORDS, 0)]), 0)
                return jnp.zeros((_b, rows), jnp.int32).at[
                    :, jnp.where(ok, i // SHARD_WORDS, rows)].add(
                        bits.astype(jnp.int32), mode="drop")

            want = np.asarray(jax.jit(jax.vmap(xla))(flat, idx, val))
            for lanes in (int(x) for x in args.lanes.split(",")):
                # no body past the largest the server compiles (8 params
                # rows at 512 lanes: kernels.step_lanes), by its gathers
                gathers = lanes // 128 * (32 // g) * b
                if L % lanes or gathers > 8 * 4 * 8 * 4:
                    continue
                fn = jax.jit(jax.vmap(
                    lambda f, i, v, _lanes=lanes: kernels._count_entries(
                        f, i, v, rows, lanes=_lanes)))
                name = f"g{g}.b{b}.{lanes}"
                t0 = time.perf_counter()
                try:
                    got = np.asarray(fn(filts, idx, val))
                except Exception as e:  # the compiler's refusal, reported
                    out["ms_a_launch"][name] = {"error": str(e)[:300]}
                    continue
                compile_s = time.perf_counter() - t0
                if not np.array_equal(got, want):
                    print(json.dumps({"wrong": [g, b, lanes]}))
                    return 1
                times = []
                for _ in range(args.iters):
                    t0 = time.perf_counter()
                    fn(filts, idx, val).block_until_ready()
                    times.append((time.perf_counter() - t0) * 1e3)
                out["ms_a_launch"][name] = {
                    "median": round(statistics.median(times), 4),
                    "min": round(min(times), 4),
                    "steps": args.shards * g * L // lanes,
                    "gathers_a_step": gathers,
                    "compile_s": round(compile_s, 2)}
    line = json.dumps(out)
    print(line)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "container_kernel_bench.json"), "w") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
