"""Benchmark: ENGINE-path PQL throughput on the BASELINE.md configs.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
"configs": {...}}.

Every number drives ``Executor.execute`` — fingerprint -> prepared plan ->
compiled XLA -> mesh dispatch -> reduce — i.e. the same path the server's
/query serves (api.py builds ``Executor(holder, use_mesh=True)``).  One
config additionally goes through the real HTTP server.

Configs (BASELINE.md):
  1. Count(Row(stargazer=r))              — single-shard Star-Trace
  2. Count(Intersect(8 rows))             — container op matrix, 1M columns
  3. TopN(language, Row(stars=r), n=50)   — ranked TopN over 10M columns
  4. Sum(Row(v > X), field=v) + GroupBy   — BSI scans over 64 shards
  5. TopN+Intersect over ~1B columns (954 shards) under a DeviceBudget
     limit sized so LRU eviction fires (BASELINE.md:30; the budget is the
     HBM analog of the reference's mmap paging).

Methodology notes:
* Every query uses DISTINCT literal values; plans are parametrized
  (executor/plan.py Slot) so distinct values share one compiled executable.
* Queries are issued as multi-call PQL batches AND multiple batches run in
  flight from concurrent client threads.  This is throughput under
  concurrent load — how the reference's own worker pool is exercised
  (executor.go:80-110); batch latency is reported separately.  The batch
  sizes and thread counts below were chosen on a remote device that no
  longer exists; ROADMAP.md S1 replaces these legs with cells timed on the
  chip.
* vs_cpu is the same workload on a single-thread numpy oracle doing the
  reference's algorithm (dense word-wise ops / bit-sliced scans) on this
  host — the stand-in for stock pilosa's CPU roaring path (BASELINE.md:
  the reference publishes no numbers).
* Engine and oracle timings are best-of-REPEATS with the relative spread
  ((max-min)/max qps across repeats) reported per config.
* Every result row names the device it ran on (platform, device_kind,
  device_count); gbps/hbm_frac are printed only on a device whose HBM peak
  is in HBM_PEAK_GBS.  A leg that raises is reported under "failed_legs"
  and main() exits non-zero.
* Config 5 data is DENSE (seg rows ~25%, metric rows ~12.5% fill, like
  SSB lineorder flag/discount rows): every 65536-column container is far
  above the 4096-bit array/bitmap threshold, so stock pilosa would hold
  bitmap containers and the word-wise AND+popcount oracle is exactly the
  reference's hot loop (roaring.go:1712 intersectionCountBitmapBitmap).
  At the sparse densities of r4's config 5 the honest roaring oracle is
  sorted-array intersection, which CPUs do faster than any dense scan —
  dense data is where a bitmap engine (and the TPU) is supposed to live.
"""

import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SEED = 7
# Peak HBM bandwidth in GB/s by jax ``device_kind``, for the gbps and
# achieved-fraction columns.  Source: Google Cloud documentation, "TPU
# v5e" (819 GB/s of HBM bandwidth per chip).  A device that is not in the
# table gets neither column.
HBM_PEAK_GBS = {"TPU v5 lite": 819.0}
REPEATS = 3  # best-of-N for engine and oracle timings (spread reported)


def best_of(fn, n=REPEATS):
    """Run ``fn`` n times; returns (best_result, spread) where ``fn``
    returns (qps, *rest) tuples, best = max qps, and spread is
    (max-min)/max across repeats."""
    runs = [fn() for _ in range(n)]
    qs = [r[0] for r in runs]
    best = max(runs, key=lambda r: r[0])
    spread = (max(qs) - min(qs)) / max(qs) if max(qs) > 0 else 0.0
    return best, round(spread, 3)


def _device_row() -> dict:
    """The device jax runs on, as it reports it — on every result row."""
    from pilosa_tpu.utils import devobs
    d = devobs.device_info()
    return {"platform": d["platform"], "device_kind": d["deviceKind"],
            "device_count": d["deviceCount"]}


def _bandwidth(qps: float, bytes_per_q: int, frac: bool = False) -> dict:
    """The gbps (and hbm_frac) columns of a row — on a device whose peak
    is known only: bytes per second of a CPU run is not a device number
    and is not printed under that name."""
    peak = HBM_PEAK_GBS.get(_device_row()["device_kind"])
    if peak is None:
        return {}
    gbps = qps * bytes_per_q / 1e9
    out = {"gbps": round(gbps, 1)}
    if frac:
        out["hbm_frac"] = round(gbps / peak, 3)
    return out


def _rand_rows(rng, n_rows, k):
    return rng.permuted(np.tile(np.arange(n_rows), (k, 1)), axis=1)[:, :8]


def _device_telemetry() -> dict:
    """Cumulative device-runtime counters (pilosa_tpu/utils/devobs.py):
    legs bracket their work with these so every BENCH_*.json row carries
    compile/retrace counts and the padding-waste ratio — the trajectory
    can then distinguish "got slower" from "started recompiling".
    Opening a bracket also RESTARTS the decode-workspace high-watermark,
    so each leg's "device" row reports its own peak, not a
    predecessor's."""
    from pilosa_tpu.utils import devobs
    c = devobs.COMPILES
    led = devobs.LEDGER
    out = {"compiles": c.compiles_total, "retraces": c.retraces_total,
           "compile_s": c.compile_seconds_total,
           "launches": led.launches_total,
           "rows": led.rows_actual_total,
           "padded": led.rows_padded_total,
           "decode_bytes": led.decode_bytes_total,
           "kernel_launches": led.kernel_launches_total}
    led.reset_decode_peak()
    return out


def _device_delta(before: dict) -> dict:
    from pilosa_tpu.utils import devobs
    # read the leg-local peak BEFORE _device_telemetry restarts it
    peak = devobs.LEDGER.decode_peak_bytes
    after = _device_telemetry()
    rows = after["rows"] - before["rows"]
    padded = after["padded"] - before["padded"]
    total = rows + padded
    return {"compiles": after["compiles"] - before["compiles"],
            "retraces": after["retraces"] - before["retraces"],
            "compile_s": round(after["compile_s"] - before["compile_s"],
                               3),
            "launches": after["launches"] - before["launches"],
            "padding_waste_ratio": round(padded / total, 4) if total
            else 0.0,
            "decode_mb": round(
                (after["decode_bytes"] - before["decode_bytes"]) / 2**20,
                2),
            "decode_peak_mb": round(peak / 2**20, 2),
            "kernel_launches": after["kernel_launches"]
            - before["kernel_launches"],
            # resolved container-kernels backend this leg ran under
            # (ops/kernels.py) — the per-leg BENCH_*.json provenance of
            # whether decode went through the Pallas kernels or jnp
            "kernel_backend": _kernel_backend()}


def _kernel_backend() -> str:
    from pilosa_tpu.ops import kernels
    return kernels.resolve()


def build_indexes():
    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.storage import FieldOptions, Holder

    rng = np.random.default_rng(SEED)
    h = Holder(None)

    # configs 1+2: single-shard, 64 rows x 200k bits (Star-Trace shaped)
    star = h.create_index("startrace", track_existence=False)
    stargazer = star.create_field("stargazer")
    n_rows, per_row = 64, 200_000
    stargazer.import_bits(
        np.repeat(np.arange(n_rows), per_row),
        rng.integers(0, SHARD_WIDTH, size=n_rows * per_row))

    # config 3: 10M columns (10 shards), 50 languages + 16-row filter field
    lang = h.create_index("lang10m", track_existence=False)
    language = lang.create_field("language")
    stars = lang.create_field("stars")
    n_bits = 2_000_000
    cols3 = rng.integers(0, 10 * SHARD_WIDTH, size=n_bits)
    language.import_bits(rng.integers(0, 50, size=n_bits), cols3)
    stars.import_bits(rng.integers(0, 16, size=n_bits), cols3)

    # GroupBy grid ride-along: two 128-row fields over 4 shards — the
    # 128x128 combo grid must run as ONE async dispatch wave (r4 verdict
    # #8; executor.GROUP_GRID_PREFIX_MAX)
    grid = h.create_index("grid4", track_existence=False)
    ga = grid.create_field("a")
    gb = grid.create_field("b")
    n_g = 400_000
    gcols = rng.integers(0, 4 * SHARD_WIDTH, size=n_g)
    ga.import_bits(rng.integers(0, 128, size=n_g), gcols)
    gb.import_bits(rng.integers(0, 128, size=n_g), gcols)

    # config 4: 64 shards, BSI int field (depth 20) + 8-row set field
    bsi_idx = h.create_index("bsi64", track_existence=False)
    v = bsi_idx.create_field("v", FieldOptions(type="int", min=0,
                                               max=1_000_000))
    seg = bsi_idx.create_field("seg")
    n_vals = 1_000_000
    cols4 = np.unique(rng.integers(0, 64 * SHARD_WIDTH, size=n_vals))
    vals4 = rng.integers(0, 1_000_000, size=cols4.size)
    v.import_values(cols4, vals4)
    seg.import_bits(rng.integers(0, 8, size=cols4.size), cols4)

    return h, {"star_rows": n_rows, "cols4": cols4, "vals4": vals4}


N_SHARDS5 = 954  # ~1B columns (954 * 2^20)


def build_config5(rng, n_shards=N_SHARDS5, sparse=False):
    """~1B-column index: 954 shards, an 8-row metric field (~12.5% fill)
    and a 4-row segment field (~25% fill) — SSB lineorder flag/discount
    shaped.  At these densities every 65536-column container is a roaring
    BITMAP container, so the CPU oracle's word-wise loop is the
    reference's own algorithm (roaring.go:1712).

    ``sparse=True`` builds the compressed-residency variant instead
    (docs/memory-budget.md): ~1.5% of words non-zero (scattered) plus one
    contiguous fully-set word range per row — the clustered + scattered
    mix of real user-id index data, where roaring would hold array/run
    containers and the packed device form compresses ~25-30x.  Same
    query/oracle surface either way.

    Rows are written densely via the Store/setRow surface
    (fragment.set_row; fragment.go setRow) — the word-level analog of
    pre-loading the benchmark index from a snapshot, sidestepping ~1e9
    single-bit import pairs on this 1-core host.  Returns (holder,
    oracle_words): oracle_words[shard] is the [12, SHARD_WORDS] uint32
    block (seg rows 0-3, then metric rows 0-7) shared by the numpy
    oracle, so engine and oracle read identical data."""
    from pilosa_tpu.core import SHARD_WORDS, VIEW_STANDARD
    from pilosa_tpu.storage import Holder

    h5 = Holder(None)
    idx = h5.create_index("ssb1b", track_existence=False)
    seg = idx.create_field("seg")
    metric = idx.create_field("metric")
    seg_view = seg._create_view_if_not_exists(VIEW_STANDARD)
    met_view = metric._create_view_if_not_exists(VIEW_STANDARD)
    oracle_words: dict[int, np.ndarray] = {}
    for shard in range(n_shards):
        a = rng.integers(0, 1 << 32, size=(12, SHARD_WORDS), dtype=np.uint32)
        b = rng.integers(0, 1 << 32, size=(12, SHARD_WORDS), dtype=np.uint32)
        words = a & b                      # ~25% fill
        words[4:] &= np.roll(b[4:], 7, axis=1)  # metric rows ~12.5%
        if sparse:
            keep = rng.random((12, SHARD_WORDS)) < 0.015
            words *= keep
            starts = rng.integers(0, SHARD_WORDS - 256, size=12)
            for r in range(12):
                words[r, starts[r]: starts[r] + 256] = 0xFFFFFFFF
        sf = seg_view.create_fragment_if_not_exists(shard)
        mf = met_view.create_fragment_if_not_exists(shard)
        for r in range(4):
            sf.set_row(r, words[r])
        for r in range(8):
            mf.set_row(r, words[4 + r])
        oracle_words[shard] = words
    return h5, oracle_words


def cpu_config5(oracle_words, shards, rng, n=2):
    """Single-thread word-wise Intersect+TopN — the roaring bitmap-
    container hot loop (roaring.go:1712 intersectionCountBitmapBitmap,
    fragment.go:1570 top) over the same words the engine reads."""
    pairs = [(int(a), int((a + 1 + rng.integers(0, 3)) % 4))
             for a in rng.integers(0, 4, size=n)]
    t0 = time.perf_counter()
    for a, b in pairs:
        counts = np.zeros(8, dtype=np.int64)
        for s in shards:
            w = oracle_words[s]
            mask = w[a] & w[b]
            for m in range(8):
                counts[m] += int(np.bitwise_count(w[4 + m] & mask).sum())
        sorted(((int(counts[m]), -m) for m in range(8)), reverse=True)[:5]
    return n / (time.perf_counter() - t0)


def oracle_topn5(oracle_words, shards, a, b, n=5):
    """Exact TopN answer for one config-5 query (for the engine
    answer-equality check)."""
    counts = np.zeros(8, dtype=np.int64)
    for s in shards:
        w = oracle_words[s]
        mask = w[a] & w[b]
        for m in range(8):
            counts[m] += int(np.bitwise_count(w[4 + m] & mask).sum())
    order = sorted(range(8), key=lambda m: (-counts[m], m))
    return [(m, int(counts[m])) for m in order[:n] if counts[m] > 0]


def _frag_bytes(executor, index, field, view="standard", rows=None):
    """Bytes one device pass reads over a field's fragments, from the LIVE
    stacked shapes (sum over shards of rows_touched * words * 4) — derived
    from holder state rather than hand-modeled constants."""
    from pilosa_tpu.core import SHARD_WORDS

    h = executor.holder
    f = h.field(index, field)
    v = f.view(view)
    total = 0
    for fr in v.fragments.values():
        total += (rows if rows is not None else fr.n_rows) * SHARD_WORDS * 4
    return total


def _run_batches(executor, index, batches, n_threads, shards_of=None):
    """Execute pre-built batch strings from ``n_threads`` concurrent client
    threads (round-robin).  Returns (qps, mean_batch_latency_s,
    p50_batch_latency_s) — BASELINE.json's metric of record is qps + p50
    latency, so the median rides along with the mean."""
    lat = []

    def run_one(i):
        t0 = time.perf_counter()
        out = executor.execute(index, batches[i],
                               shards=None if shards_of is None
                               else shards_of[i])
        lat.append(time.perf_counter() - t0)
        return len(out)

    t0 = time.perf_counter()
    with ThreadPoolExecutor(n_threads) as pool:
        counts = list(pool.map(run_one, range(len(batches))))
    dt = time.perf_counter() - t0
    return (sum(counts) / dt, sum(lat) / len(lat),
            float(np.median(lat)))


def bench_config1(executor, meta, rng):
    # B=32768 amortizes per-batch host cost over enough queries that the
    # native fingerprint scan (+ one fetch round trip) stays under the
    # per-query budget.  Sized on a remote device that no longer exists;
    # not re-derived on the chip (ROADMAP.md S1 replaces this leg)
    B, n_batches, T = 32768, 16, 8

    def batch():
        rows = rng.integers(0, meta["star_rows"], size=B)
        return " ".join(f"Count(Row(stargazer={r}))" for r in rows)

    executor.execute("startrace", batch())  # warm compile + stacks

    def run():
        batches = [batch() for _ in range(n_batches)]
        return _run_batches(executor, "startrace", batches, T)

    (qps, bat_s, p50_s), spread = best_of(run)
    # one row segment read per query
    bytes_per_q = _frag_bytes(executor, "startrace", "stargazer", rows=1)
    return qps, bat_s, p50_s, bytes_per_q, spread


def bench_config2(executor, meta, rng):
    B, n_batches, T = 4096, 32, 32
    n_rows = meta["star_rows"]

    def batch():
        sets = _rand_rows(rng, n_rows, B)
        return " ".join(
            "Count(Intersect(" + ", ".join(
                f"Row(stargazer={r})" for r in q) + "))"
            for q in sets)

    executor.execute("startrace", batch())

    def run():
        batches = [batch() for _ in range(n_batches)]
        return _run_batches(executor, "startrace", batches, T)

    (qps, bat_s, p50_s), spread = best_of(run)
    # 8 row segments streamed per query
    bytes_per_q = _frag_bytes(executor, "startrace", "stargazer", rows=8)
    return qps, bat_s, p50_s, bytes_per_q, spread


def bench_config3(executor, meta, rng):
    B, n_batches, T = 128, 32, 16

    def batch():
        rs = rng.integers(0, 16, size=B)
        return " ".join(f"TopN(language, Row(stars={r}), n=50)" for r in rs)

    executor.execute("lang10m", batch())

    def run():
        batches = [batch() for _ in range(n_batches)]
        return _run_batches(executor, "lang10m", batches, T)

    (qps, bat_s, p50_s), spread = best_of(run)
    # per query: full language fragment pass + one stars row per shard
    bytes_per_q = _frag_bytes(executor, "lang10m", "language") + \
        _frag_bytes(executor, "lang10m", "stars", rows=1)
    return qps, bat_s, p50_s, bytes_per_q, spread


def bench_config4(executor, meta, rng):
    B, n_batches, T = 64, 24, 12

    def batch():
        xs = rng.integers(0, 1_000_000, size=B)
        return " ".join(f"Sum(Row(v > {int(x)}), field=v)" for x in xs)

    executor.execute("bsi64", batch())

    def run():
        batches = [batch() for _ in range(n_batches)]
        return _run_batches(executor, "bsi64", batches, T)

    (qps, bat_s, p50_s), spread = best_of(run)
    # per query: ONE fused pass over the BSI fragment (XLA fuses the range
    # scan and the masked slice popcounts into a single read of the
    # stacked block)
    bytes_per_q = _frag_bytes(executor, "bsi64", "v", view="bsig_v")
    # GroupBy ride-along: 8x8 combo grid + BSI filter in ONE executable
    # invocation; the timed run uses a DISTINCT filter literal so the
    # remote-device memoization cannot serve a cached answer
    executor.execute("bsi64", "GroupBy(Rows(seg), Rows(seg), Row(v > 1))")
    t0 = time.perf_counter()
    executor.execute("bsi64",
                     "GroupBy(Rows(seg), Rows(seg), Row(v > 500000))")
    gb_s = time.perf_counter() - t0
    # 128x128 two-field grid in one dispatch wave (grid4 index); the
    # timed run varies a parametrized filter literal so the result cache
    # cannot serve the answer while the executable stays compiled
    executor.execute("grid4", "GroupBy(Rows(a), Rows(b), Row(b=1))")
    t0 = time.perf_counter()
    executor.execute("grid4", "GroupBy(Rows(a), Rows(b), Row(b=7))")
    gb_grid_s = time.perf_counter() - t0
    return qps, bat_s, p50_s, bytes_per_q, gb_s, gb_grid_s, spread


def _cfg5_batch(rng, B):
    """B distinct Intersect+TopN calls (SSB flagship query shape,
    executor.go:2414-2552)."""
    aa = rng.integers(0, 4, size=B)
    bb = (aa + 1 + rng.integers(0, 3, size=B)) % 4
    return " ".join(
        f"TopN(metric, Intersect(Row(seg={a}), Row(seg={b})), n=5)"
        for a, b in zip(aa, bb))


def bench_config5(ex5, oracle_words, rng, budget_mb, resident):
    """Intersect+TopN over ~1B columns (954 shards, 4 rotating shard
    subsets).

    ``resident=True``: budget sized so all 4 subset stacks stay
    HBM-resident — the realistic v5e operating point, with vs_cpu against
    the word-wise roaring oracle.  ``resident=False``: budget deliberately
    below one rotation's working set so LRU eviction must fire — the
    HBM-pressure stress variant (the reference's mmap-paging analog)."""
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET

    budget = budget_mb << 20
    old_limit = DEFAULT_BUDGET.limit_bytes
    DEFAULT_BUDGET.limit_bytes = budget
    DEFAULT_BUDGET.shrink_to_limit()
    DEFAULT_BUDGET.reset_peak()
    ev0 = DEFAULT_BUDGET.evictions
    try:
        subsets = np.array_split(np.arange(N_SHARDS5), 4)
        subsets = [list(map(int, s)) for s in subsets]
        if resident:
            B, nb, T, reps = 64, 24, 8, REPEATS
            order = [subsets[i % 4] for i in range(nb)]
        else:
            # hot subset alternating with rotating cold subsets: cache-
            # working-set pattern that forces eviction under the budget
            B, nb, T, reps = 32, 12, 1, 1
            order = [subsets[0] if i % 2 == 0
                     else subsets[1 + (i // 2) % 3] for i in range(nb)]
        # warm: compile once + stage each subset's stacks
        for sub in subsets:
            ex5.execute("ssb1b", _cfg5_batch(rng, B), shards=sub)

        def run():
            batches = [_cfg5_batch(rng, B) for _ in range(nb)]
            return _run_batches(ex5, "ssb1b", batches, T, shards_of=order)

        (qps, bat_s, p50_s), spread = best_of(run, n=reps)
        stats = DEFAULT_BUDGET.stats()
        # per query: one pass over the subset's metric+seg stacked rows
        rows_touched = 8 + 4
        bytes_per_q = len(subsets[0]) * rows_touched * 32768 * 4
        out = {
            "qps": round(qps, 1),
            "batch_ms": round(bat_s * 1e3, 1),
            "batch_p50_ms": round(p50_s * 1e3, 1),
            "spread": spread,
            **_bandwidth(qps, bytes_per_q, frac=resident),
            "columns": N_SHARDS5 << 20,
            "budget_mb": budget_mb,
            "peak_mb": stats["peakBytes"] >> 20,
            "resident_mb": stats["residentBytes"] >> 20,
            "evictions": DEFAULT_BUDGET.evictions - ev0,
            "budget_held": stats["peakBytes"] <= budget,
        }
        # oracle over one rotation subset (same shards the engine hits)
        (oracle_qps,), o_spread = best_of(
            lambda: (cpu_config5(oracle_words, subsets[0], rng),),
            n=min(reps, 2))
        out["vs_cpu"] = round(qps / oracle_qps, 2)
        out["cpu_qps"] = round(oracle_qps, 2)
        out["cpu_spread"] = o_spread
        return out
    finally:
        DEFAULT_BUDGET.limit_bytes = old_limit


def bench_config5_compressed(rng, n_shards=N_SHARDS5, budget_mb=768,
                             B=32, nb=12, reps=1):
    """The over-budget cliff, compressed vs dense (docs/memory-budget.md
    "Compressed residency"): the SPARSE ~1B-col corpus (the data shape
    compressed residency exists for) queried over rotating shard subsets
    under a budget deliberately below one rotation's dense working set.

    Three sub-legs on identical data and identical queries:
      * ``resident``   — dense form, unlimited budget: the qps anchor.
      * ``dense``      — dense form, over-budget: today's cliff (stream +
                         evict every rotation).
      * ``compressed`` — packed container streams under the same budget:
                         the working set fits, rotation is free.
    Reports compressed_mb, the effective-capacity ratio (dense bytes per
    compressed byte actually staged), and each leg's cliff vs the
    resident anchor."""
    from pilosa_tpu.executor import Executor as _Ex
    from pilosa_tpu.storage import fragment as _frag
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET

    h5, oracle_words = build_config5(rng, n_shards=n_shards, sparse=True)
    ex = _Ex(h5, use_mesh=True)
    old_limit = DEFAULT_BUDGET.limit_bytes
    old_form = _frag.COMPRESSED_RESIDENT
    subsets = [list(map(int, s))
               for s in np.array_split(np.arange(n_shards), 4)]
    dense_set_mb = (n_shards * 12 * 32768 * 4) >> 20
    out = {"columns": n_shards << 20, "budget_mb": budget_mb,
           "dense_working_set_mb": dense_set_mb, "sparse": True}

    def leg(compressed, limit_mb):
        _frag.COMPRESSED_RESIDENT = compressed
        # flush residency from the previous leg so each leg's
        # resident/compressed gauges describe only its own staging
        DEFAULT_BUDGET.limit_bytes = 1
        DEFAULT_BUDGET.shrink_to_limit()
        DEFAULT_BUDGET.limit_bytes = \
            None if limit_mb is None else limit_mb << 20
        DEFAULT_BUDGET.reset_peak()
        ev0 = DEFAULT_BUDGET.evictions
        # hot subset alternating with rotating cold subsets — the
        # working-set pattern that makes an over-budget dense form
        # evict + re-stage every other batch
        order = [subsets[0] if i % 2 == 0
                 else subsets[1 + (i // 2) % 3] for i in range(nb)]
        for sub in subsets:  # warm: compile + stage
            ex.execute("ssb1b", _cfg5_batch(rng, B), shards=sub)

        def run():
            batches = [_cfg5_batch(rng, B) for _ in range(nb)]
            return _run_batches(ex, "ssb1b", batches, 1, shards_of=order)

        (qps, _bat_s, p50_s), spread = best_of(run, n=reps)
        stats = DEFAULT_BUDGET.stats()
        return {
            "qps": round(qps, 1),
            "batch_p50_ms": round(p50_s * 1e3, 1),
            "spread": spread,
            "evictions": DEFAULT_BUDGET.evictions - ev0,
            "resident_mb": stats["residentBytes"] >> 20,
            "compressed_mb": round(stats["compressedBytes"] / 2**20, 1),
            "peak_mb": stats["peakBytes"] >> 20,
            "budget_held": limit_mb is None or
            stats["peakBytes"] <= (limit_mb << 20),
        }

    try:
        # answer-equality in BOTH forms before any timing
        q = "TopN(metric, Intersect(Row(seg=0), Row(seg=2)), n=5)"
        want = oracle_topn5(oracle_words, range(n_shards), 0, 2)
        for form in (False, True):
            _frag.COMPRESSED_RESIDENT = form
            DEFAULT_BUDGET.limit_bytes = budget_mb << 20
            DEFAULT_BUDGET.shrink_to_limit()
            got = ex.execute("ssb1b", q)
            assert [(p.id, p.count) for p in got[0]] == want, \
                f"compressed={form} answer diverged from the oracle"

        out["resident"] = leg(False, None)
        out["dense"] = leg(False, budget_mb)
        out["compressed"] = leg(True, budget_mb)
        anchor = out["resident"]["qps"]
        if anchor > 0:
            out["dense"]["cliff_vs_resident"] = round(
                anchor / max(out["dense"]["qps"], 1e-9), 1)
            out["compressed"]["cliff_vs_resident"] = round(
                anchor / max(out["compressed"]["qps"], 1e-9), 1)
        comp_mb = out["compressed"]["compressed_mb"]
        if comp_mb > 0:
            out["effective_capacity_ratio"] = round(
                dense_set_mb / comp_mb, 1)
        return out
    finally:
        _frag.COMPRESSED_RESIDENT = old_form
        DEFAULT_BUDGET.limit_bytes = old_limit
        ex.close()


# -- SSB star-schema workload (docs/architecture.md "On native code and
# Pallas"; the r10 on-TPU round's main leg) ---------------------------------

N_SHARDS_SSB = 256  # ~268M fact rows at the 2^20-shard geometry

# (field, rows): the denormalized dimension columns of an SSB lineorder
# fact table, bitmap-encoded — each field partitions every fact column
# into one selective row (d_year buckets, region/category codes) — plus
# an 8-bucket revenue measure for the TopN/GroupBy legs.
SSB_FIELDS = (("year", 7), ("region", 5), ("category", 12), ("rev", 8))


def build_ssb(rng, n_shards=N_SHARDS_SSB, sparse=True):
    """Wide denormalized star-schema fact index, SSB-shaped: one row of
    ``ssb`` per fact, every dimension attribute denormalized onto it as
    a selective Row (the reference's canonical star-join modeling —
    dimension filters become Row intersects, no join machinery).  Every
    column belongs to exactly one row per field, assigned in 32-column
    blocks so the word-wise numpy oracle is exact.

    ``sparse=True`` (default) keeps only ~1.5% of fact columns plus one
    contiguous fully-populated region per shard — the scattered +
    clustered mix the compressed container forms exist for, giving the
    compressed-over-budget sub-leg array AND run containers to decode.
    Returns (holder, ssb_words): ssb_words[shard] maps field ->
    [rows, SHARD_WORDS] uint32 oracle block."""
    from pilosa_tpu.core import SHARD_WORDS, VIEW_STANDARD
    from pilosa_tpu.storage import Holder

    h = Holder(None)
    idx = h.create_index("ssb", track_existence=False)
    views = {}
    for name, _rows in SSB_FIELDS:
        f = idx.create_field(name)
        views[name] = f._create_view_if_not_exists(VIEW_STANDARD)
    ssb_words: dict[int, dict[str, np.ndarray]] = {}
    for shard in range(n_shards):
        if sparse:
            live = (rng.random(SHARD_WORDS) < 0.015).astype(np.uint32)
            live *= np.uint32(0xFFFFFFFF)
            start = int(rng.integers(0, SHARD_WORDS - 512))
            live[start: start + 512] = 0xFFFFFFFF
        else:
            live = np.full(SHARD_WORDS, 0xFFFFFFFF, dtype=np.uint32)
        per_field = {}
        for name, n_rows in SSB_FIELDS:
            assign = rng.integers(0, n_rows, size=SHARD_WORDS)
            words = np.zeros((n_rows, SHARD_WORDS), dtype=np.uint32)
            for r in range(n_rows):
                words[r, assign == r] = 0xFFFFFFFF
            words &= live[None, :]
            fr = views[name].create_fragment_if_not_exists(shard)
            for r in range(n_rows):
                fr.set_row(r, words[r])
            per_field[name] = words
        ssb_words[shard] = per_field
    return h, ssb_words


def _ssb_batch(rng, B):
    """B calls cycling the three SSB query shapes: Q1-style restricted
    Count (Intersect of two dimension rows), Q2-style TopN of the
    revenue measure under a dimension filter, Q3-style two-dimension
    GroupBy under a region filter."""
    out = []
    for kind in rng.integers(0, 3, size=B):
        y = rng.integers(0, 7)
        rg = rng.integers(0, 5)
        c = rng.integers(0, 12)
        if kind == 0:
            out.append(f"Count(Intersect(Row(year={y}), "
                       f"Row(region={rg})))")
        elif kind == 1:
            out.append(f"TopN(rev, Intersect(Row(region={rg}), "
                       f"Row(category={c})), n=5)")
        else:
            out.append(f"GroupBy(Rows(year), Rows(region), "
                       f"Row(category={c}))")
    return " ".join(out)


def _ssb_norm(results):
    """Mixed SSB results (Count ints, TopN Pairs, GroupBy GroupCounts)
    -> comparable plain values; _smoke_norm is TopN-only."""
    return [[p.to_dict() for p in r] if isinstance(r, list) else r
            for r in results]


def oracle_ssb_topn(ssb_words, shards, rg, c, n=5):
    """Exact word-wise answer for the Q2-style TopN (the SSB
    answer-equality gate, like oracle_topn5 for config 5)."""
    counts = np.zeros(8, dtype=np.int64)
    for s in shards:
        w = ssb_words[s]
        mask = w["region"][rg] & w["category"][c]
        for m in range(8):
            counts[m] += int(np.bitwise_count(w["rev"][m] & mask).sum())
    order = sorted(range(8), key=lambda m: (-counts[m], m))
    return [(m, int(counts[m])) for m in order[:n] if counts[m] > 0]


def bench_ssb(rng, n_shards=N_SHARDS_SSB, budget_mb=96, B=24, nb=8,
              reps=1):
    """SSB star-schema main leg: the sparse fact corpus queried with the
    three SSB shapes, as two sub-legs on identical data/queries —
    ``resident`` (dense form, unlimited budget: the anchor) vs
    ``compressed`` (packed container streams under a budget below the
    dense working set, decoding per launch through whatever
    container-kernels backend the process resolved — recorded per leg in
    ``device.kernel_backend``).  Runnable unchanged on real TPU, where
    the compressed sub-leg exercises the fused Pallas kernels."""
    from pilosa_tpu.executor import Executor as _Ex
    from pilosa_tpu.storage import fragment as _frag
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET

    h, ssb_words = build_ssb(rng, n_shards=n_shards, sparse=True)
    ex = _Ex(h, use_mesh=True)
    old_limit = DEFAULT_BUDGET.limit_bytes
    old_form = _frag.COMPRESSED_RESIDENT
    n_rows_total = sum(r for _, r in SSB_FIELDS)
    dense_set_mb = (n_shards * n_rows_total * 32768 * 4) >> 20
    out = {"columns": n_shards << 20, "budget_mb": budget_mb,
           "dense_working_set_mb": dense_set_mb,
           "fields": dict(SSB_FIELDS)}
    subsets = [list(map(int, s))
               for s in np.array_split(np.arange(n_shards), 4)]

    def leg(compressed, limit_mb):
        _frag.COMPRESSED_RESIDENT = compressed
        DEFAULT_BUDGET.limit_bytes = 1
        DEFAULT_BUDGET.shrink_to_limit()
        DEFAULT_BUDGET.limit_bytes = \
            None if limit_mb is None else limit_mb << 20
        DEFAULT_BUDGET.reset_peak()
        for sub in subsets:  # warm: compile + stage
            ex.execute("ssb", _ssb_batch(rng, B), shards=sub)
        dev0 = _device_telemetry()

        def run():
            batches = [_ssb_batch(rng, B) for _ in range(nb)]
            order = [subsets[i % 4] for i in range(nb)]
            return _run_batches(ex, "ssb", batches, 1, shards_of=order)

        (qps, _bat_s, p50_s), spread = best_of(run, n=reps)
        stats = DEFAULT_BUDGET.stats()
        return {
            "qps": round(qps, 1),
            "batch_p50_ms": round(p50_s * 1e3, 1),
            "spread": spread,
            "resident_mb": stats["residentBytes"] >> 20,
            "compressed_mb": round(stats["compressedBytes"] / 2**20, 1),
            "budget_held": limit_mb is None or
            stats["peakBytes"] <= (limit_mb << 20),
            "device": _device_delta(dev0),
        }

    try:
        # answer-equality in both forms before any timing
        q = "TopN(rev, Intersect(Row(region=1), Row(category=3)), n=5)"
        want = oracle_ssb_topn(ssb_words, range(n_shards), 1, 3)
        for form in (False, True):
            _frag.COMPRESSED_RESIDENT = form
            DEFAULT_BUDGET.limit_bytes = budget_mb << 20
            DEFAULT_BUDGET.shrink_to_limit()
            got = ex.execute("ssb", q)
            assert [(p.id, p.count) for p in got[0]] == want, \
                f"ssb compressed={form} answer diverged from the oracle"

        out["resident"] = leg(False, None)
        out["compressed"] = leg(True, budget_mb)
        anchor = out["resident"]["qps"]
        if anchor > 0:
            out["compressed"]["cliff_vs_resident"] = round(
                anchor / max(out["compressed"]["qps"], 1e-9), 1)
        comp_mb = out["compressed"]["compressed_mb"]
        if comp_mb > 0:
            out["effective_capacity_ratio"] = round(
                dense_set_mb / comp_mb, 1)
        return out
    finally:
        _frag.COMPRESSED_RESIDENT = old_form
        DEFAULT_BUDGET.limit_bytes = old_limit
        ex.close()


def run_ssb_smoke(rng) -> dict:
    """SSB leg of --smoke: the star-schema corpus at 8 shards run
    dense-resident (reference), compressed-jnp, and compressed-PALLAS
    (interpreted on CPU — the same kernels a TPU compiles), asserting
    all three byte-identical, at least one container-kernel launch in
    the pallas leg's ledger bracket, and none in the jnp kill-switch
    leg."""
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.ops import kernels
    from pilosa_tpu.storage import fragment as _frag
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET

    n_shards = 8
    h, ssb_words = build_ssb(rng, n_shards=n_shards, sparse=True)
    ex = Executor(h, use_mesh=True)
    old_limit = DEFAULT_BUDGET.limit_bytes
    old_form = _frag.COMPRESSED_RESIDENT
    old_backend = kernels.CONTAINER_KERNELS
    batches = [_ssb_batch(rng, 6) for _ in range(3)]
    full_q = "TopN(rev, Intersect(Row(region=1), Row(category=3)), n=5)"
    out = {}
    try:
        _frag.COMPRESSED_RESIDENT = False
        DEFAULT_BUDGET.limit_bytes = None
        want = [_ssb_norm(ex.execute("ssb", b)) for b in batches]
        assert _smoke_norm(ex.execute("ssb", full_q))[0] == \
            oracle_ssb_topn(ssb_words, range(n_shards), 1, 3), \
            "ssb dense answer diverged from the oracle"

        _frag.COMPRESSED_RESIDENT = True
        DEFAULT_BUDGET.limit_bytes = 16 << 20
        for backend in ("jnp", "pallas"):
            kernels.CONTAINER_KERNELS = backend
            DEFAULT_BUDGET.shrink_to_limit()
            dev0 = _device_telemetry()
            t0 = time.perf_counter()
            got = [_ssb_norm(ex.execute("ssb", b)) for b in batches]
            leg_s = time.perf_counter() - t0
            dev = _device_delta(dev0)
            assert got == want, \
                f"ssb compressed-{backend} results diverged from the " \
                f"dense run"
            assert dev["kernel_backend"] == backend
            if backend == "pallas":
                assert dev["kernel_launches"] > 0, \
                    "pallas leg never launched a container kernel"
            else:
                assert dev["kernel_launches"] == 0, \
                    "jnp kill-switch leg launched container kernels"
            out[backend] = {"leg_s": round(leg_s, 2), "device": dev}
        st = DEFAULT_BUDGET.stats()
        assert st["compressedBytes"] > 0, \
            "ssb smoke never staged a packed stream"
        out["compressed_mb"] = round(st["compressedBytes"] / 2**20, 2)
        return out
    finally:
        kernels.CONTAINER_KERNELS = old_backend
        _frag.COMPRESSED_RESIDENT = old_form
        DEFAULT_BUDGET.limit_bytes = old_limit
        ex.close()


N_SHARDS5D = 256  # ~268M columns over 4 nodes


def bench_config5_distributed(rng):
    """BASELINE config 5's cluster half: 4 real server nodes in-process
    (sharing the one local accelerator), dense SSB-shaped data loaded
    through the binary roaring import surface, queries fanned out as
    pinned multi-call batches and reduced over real HTTP
    (executor.go:2414-2552 scatter/gather).  The measured load is a
    RECORDED mixed-workload replay: a varied workload (TopN batches,
    Count(Intersect), Row fetches) runs once with the slow-query
    threshold dropped to ~0 so the PR 5 slow-log ring records every
    query, and the recorded texts are then replayed as the measured
    corpus — traffic shaped like what the cluster actually served, not
    synthetic-uniform batches.  Publishes vs_cpu against the same
    word-wise oracle as config 5 plus the coordinator's
    device/wire/reduce latency breakdown from /debug/vars."""
    import http.client
    import socket
    import tempfile

    from pilosa_tpu.core import SHARD_WIDTH, SHARD_WORDS
    from pilosa_tpu.server import Config, Server
    from pilosa_tpu.storage.roaring_io import pack_roaring_words

    socks = []
    for _ in range(4):
        s = socket.socket()
        s.bind(("localhost", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    hosts = [f"localhost:{p}" for p in ports]
    servers = []

    def req(port, method, path, body: bytes | None = None, timeout=300):
        conn = http.client.HTTPConnection("localhost", port,
                                          timeout=timeout)
        conn.request(method, path, body=body)
        resp = conn.getresponse()
        data = resp.read()
        conn.close()
        if resp.status != 200:
            raise RuntimeError(f"{path}: {resp.status} {data[:200]!r}")
        return data

    def post(port, path, body: bytes, timeout=300):
        return req(port, "POST", path, body, timeout=timeout)

    try:
        for i, p in enumerate(ports):
            srv = Server(Config(
                data_dir=tempfile.mkdtemp(prefix=f"ptpu_b5d_{i}_"),
                bind=hosts[i], node_id=f"node{i}", cluster_hosts=hosts,
                replica_n=1, anti_entropy_interval=0,
                slow_log_size=2048))
            servers.append(srv)  # before open: finally closes partials
            srv.open()
        p0 = ports[0]
        post(p0, "/index/dist", b"{}")
        post(p0, "/index/dist/field/seg", b"{}")
        post(p0, "/index/dist/field/metric", b"{}")
        # dense data, same shape/density as config 5 (seg rows ~25%,
        # metric rows ~12.5%): bitmap-container regime where the CPU
        # oracle is the reference's word-wise hot loop.  Loaded per shard
        # through the binary roaring import endpoint (the reference's
        # /import-roaring surface), which forwards to the shard's owner.
        oracle_words: dict[int, np.ndarray] = {}
        for shard in range(N_SHARDS5D):
            a = rng.integers(0, 1 << 32, size=(12, SHARD_WORDS),
                             dtype=np.uint32)
            b = rng.integers(0, 1 << 32, size=(12, SHARD_WORDS),
                             dtype=np.uint32)
            words = a & b
            words[4:] &= np.roll(b[4:], 7, axis=1)
            oracle_words[shard] = words
            post(p0, f"/index/dist/field/seg/import-roaring/{shard}",
                 pack_roaring_words(words[:4]))
            post(p0, f"/index/dist/field/metric/import-roaring/{shard}",
                 pack_roaring_words(words[4:]))

        B, n_batches, T = 64, 16, 8

        def batch():
            return _cfg5_batch(rng, B)

        # warm every node's compile + stacks FIRST: the initial queries
        # pay each node's XLA compile plus
        # ~100MB/node of stack staging, so they get a generous timeout;
        # heavy imports can also make health probes time out and mark
        # peers DOWN transiently
        for attempt in range(6):
            try:
                for p in ports:
                    post(p, "/index/dist/query", batch().encode(),
                         timeout=1800)
                break
            except (RuntimeError, OSError):
                if attempt == 5:
                    raise
                time.sleep(4)

        # answer-equality: cluster TopN == word-wise oracle over all
        # shards (r4 weak #3: the distributed config had no oracle)
        got = json.loads(post(
            p0, "/index/dist/query",
            b"TopN(metric, Intersect(Row(seg=1), Row(seg=3)), n=5)",
            timeout=1800))
        want = oracle_topn5(oracle_words, range(N_SHARDS5D), 1, 3)
        got_pairs = [(p["id"], p["count"]) for p in got["results"][0]]
        assert got_pairs == want, f"5d mismatch: {got_pairs} != {want}"

        # -- record phase (docs/cluster.md; the PR 5 slow-log corpus):
        # drop every node's slow threshold to ~0 so the ring records the
        # whole mixed workload — TopN batches plus Count(Intersect) and
        # Row singles — then harvest the recorded query texts as the
        # replay corpus and restore the threshold before measuring
        for srv in servers:
            srv.slowlog.threshold_s = 1e-9
        # the slow log marks over-ceiling entries textTruncated
        # (slow-log-text-max knob): the harvester skips those BY FLAG —
        # a truncated batch replays as a parse error, and the old
        # length-heuristic filter silently depended on the exact
        # ceiling value
        mixed = [_cfg5_batch(rng, 4) for _ in range(12)]
        for i in range(16):
            a = int(rng.integers(0, 4))
            b = (a + 1 + int(rng.integers(0, 3))) % 4
            mixed.append(
                f"Count(Intersect(Row(seg={a}), Row(seg={b})))"
                if i % 2 else f"Row(seg={a})")
        for i, m in enumerate(mixed):
            post(ports[i % 4], "/index/dist/query", m.encode(),
                 timeout=1800)
        corpus = []
        for p in ports:
            slow = json.loads(req(p, "GET", "/debug/slow"))
            corpus.extend(
                e["query"] for e in slow.get("entries", [])
                if e.get("index") == "dist" and e.get("query")
                and not e.get("textTruncated"))
        assert len(corpus) >= len(mixed), \
            f"slow-log recorded only {len(corpus)} of {len(mixed)}"
        for srv in servers:
            srv.slowlog.threshold_s = 1.0
        calls_per_replay = sum(max(q.count("TopN("), 1) for q in corpus)

        # baseline the timing counters AFTER warm-up: the warm waves pay
        # each node's XLA compile (seconds), which must not pollute the
        # per-wave averages published below
        snap0 = json.loads(req(p0, "GET", "/debug/vars"))
        t0s = snap0.get("timings", {})

        def run():
            batches = [(ports[i % 4], corpus[i % len(corpus)].encode())
                       for i in range(len(corpus))]
            lats = []

            def post_one(pb):
                t1 = time.perf_counter()
                post(pb[0], "/index/dist/query", pb[1])
                lats.append(time.perf_counter() - t1)

            t0 = time.perf_counter()
            with ThreadPoolExecutor(T) as pool:
                list(pool.map(post_one, batches))
            return (calls_per_replay / (time.perf_counter() - t0),
                    float(np.median(lats)))

        (qps, p50_s), spread = best_of(run)
        (oracle_qps,), _ = best_of(
            lambda: (cpu_config5(oracle_words, range(N_SHARDS5D), rng),),
            n=2)
        # coordinator-side breakdown (avg ms per fan-out wave, timed
        # waves only: post-warm delta of the cumulative counters)
        snap = json.loads(req(p0, "GET", "/debug/vars"))
        timings = snap.get("timings", {})

        def avg_ms(name):
            t = timings.get(name)
            if not t or not t.get("count"):
                return None
            base = t0s.get(name, {"count": 0, "sum": 0.0})
            cnt = t["count"] - base.get("count", 0)
            tot = t["sum"] - base.get("sum", 0.0)
            return round(1e3 * tot / cnt, 2) if cnt > 0 else None

        return {
            "qps": round(qps, 1),
            "batch_p50_ms": round(p50_s * 1e3, 1),
            "spread": spread,
            "nodes": 4,
            "workload": "recorded_replay",
            "corpus_queries": len(corpus),
            "columns": N_SHARDS5D * SHARD_WIDTH,
            "vs_cpu": round(qps / oracle_qps, 2),
            "cpu_qps": round(oracle_qps, 2),
            "breakdown_avg_ms": {
                "peer_exec": avg_ms("cluster.multi.peer_exec"),
                "wire_overhead": avg_ms("cluster.multi.wire_overhead"),
                "local_exec": avg_ms("cluster.multi.local_exec"),
                "reduce": avg_ms("cluster.multi.reduce"),
            },
        }
    finally:
        for s in servers:
            try:
                s.close()
            # lint: allow(swallowed-exception) — bench teardown; the
            # server may already be down and the leg's numbers are in
            except Exception:
                pass


def _routing_leg(rng, *, n_cold_shards=6, waves=4, wave_q=64, threads=8,
                 hot_bits=6000, cold_bits=4000):
    """Elastic-serving leg (docs/cluster.md "Read routing &
    rebalancing"): 3 real server nodes in-process, replica_n=2, and a
    SKEWED workload — ~80% of queries hit a hot 2-shard index, the rest
    spread over a cold index — replayed under read-routing=primary
    (reads pinned to the jump-hash primary, the pre-PR-13 behavior) and
    then read-routing=loaded.  Asserts the two runs answer byte-
    identically and reports qps for both plus the per-shard replica
    spread (how many nodes served each hot shard under loaded — the
    idle-replica signal this subsystem exists to fix)."""
    import http.client
    import socket
    import tempfile
    import threading

    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.server import Config, Server

    socks = []
    for _ in range(3):
        s = socket.socket()
        s.bind(("localhost", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    hosts = [f"localhost:{p}" for p in ports]
    servers = []

    def post(port, path, body: bytes, timeout=600):
        conn = http.client.HTTPConnection("localhost", port,
                                          timeout=timeout)
        conn.request("POST", path, body=body)
        resp = conn.getresponse()
        data = resp.read()
        conn.close()
        if resp.status != 200:
            raise RuntimeError(f"{path}: {resp.status} {data[:200]!r}")
        return json.loads(data)

    try:
        for i, p in enumerate(ports):
            srv = Server(Config(
                data_dir=tempfile.mkdtemp(prefix=f"ptpu_rt_{i}_"),
                bind=hosts[i], node_id=f"node{i}", cluster_hosts=hosts,
                replica_n=2, anti_entropy_interval=0))
            servers.append(srv)
            srv.open()
        p0 = ports[0]
        for name, n_shards, n_bits in (("hotidx", 2, hot_bits),
                                       ("coldidx", n_cold_shards,
                                        cold_bits)):
            post(p0, f"/index/{name}", b"{}")
            post(p0, f"/index/{name}/field/a", b"{}")
            cols = np.unique(rng.integers(0, n_shards * SHARD_WIDTH,
                                          size=n_bits))
            rows = rng.integers(0, 8, size=cols.size)
            post(p0, f"/index/{name}/field/a/import", json.dumps({
                "rowIDs": rows.tolist(),
                "columnIDs": cols.tolist()}).encode())

        def gen_q():
            a = int(rng.integers(0, 8))
            b = (a + 1 + int(rng.integers(0, 6))) % 8
            hot = rng.random() < 0.8
            idx = "hotidx" if hot else "coldidx"
            kind = int(rng.integers(0, 4))
            if kind == 0:
                q = f"Count(Intersect(Row(a={a}), Row(a={b})))"
            elif kind == 1:
                q = f"Count(Row(a={a}))"
            elif kind == 2:
                q = f"Row(a={a})"
            else:
                q = "TopN(a, n=0)"  # exact cluster reduce
            return idx, q

        corpus = [gen_q() for _ in range(wave_q)]
        # warm every node's compiles before timing
        for p in ports:
            for idx, q in corpus[:6]:
                post(p, f"/index/{idx}/query", q.encode(), timeout=1800)
        coord = servers[0].cluster

        def run(policy):
            for srv in servers:
                srv.cluster.router.policy = policy
            coord.load_tracker.rotate()
            coord.load_tracker.rotate()
            answers = {}
            lock = threading.Lock()

            def post_one(item):
                i, (idx, q) = item
                out = post(p0, f"/index/{idx}/query", q.encode())
                with lock:
                    answers[i % wave_q] = out["results"]

            items = [(i, corpus[i % wave_q])
                     for i in range(waves * wave_q)]
            t0 = time.perf_counter()
            with ThreadPoolExecutor(threads) as pool:
                list(pool.map(post_one, items))
            qps = len(items) / (time.perf_counter() - t0)
            return qps, answers

        qps_primary, ans_primary = run("primary")
        qps_loaded, ans_loaded = run("loaded")
        assert ans_loaded == ans_primary, \
            "loaded routing diverged from primary-pinned answers"
        # per-shard replica spread on the hot index under loaded
        snap = coord.load_tracker.snapshot(top=32)
        spread = {e["shard"]: len(e["nodes"]) for e in snap["hottest"]
                  if e["index"] == "hotidx"}
        return {
            "answers_identical": True,
            "qps_primary": round(qps_primary, 1),
            "qps_loaded": round(qps_loaded, 1),
            "loaded_vs_primary": round(qps_loaded / qps_primary, 3)
            if qps_primary else None,
            "hot_shard_nodes": max(spread.values(), default=0),
            "hot_shard_spread": spread,
            "fallbacks": servers[0].cluster.router.snapshot()["fallbacks"],
        }
    finally:
        for s in servers:
            try:
                s.close()
            # lint: allow(swallowed-exception) — bench teardown; the
            # server may already be down and the leg's numbers are in
            except Exception:
                pass


def bench_routing(rng):
    """Main-bench elastic-serving leg: the skewed-hot-index corpus at
    full wave counts (see _routing_leg)."""
    return _routing_leg(rng, waves=6, wave_q=64, threads=8)


def run_routing_smoke(rng) -> dict:
    """Routing leg of --smoke (docs/cluster.md): the skew corpus small —
    routing-on (loaded) vs primary-pinned qps, answers asserted
    identical, and the hot shards served by more than one node."""
    out = _routing_leg(rng, waves=3, wave_q=24, threads=8,
                       hot_bits=2500, cold_bits=1500, n_cold_shards=4)
    assert out["hot_shard_nodes"] > 1, \
        f"hot shards never spread: {out['hot_shard_spread']}"
    return out


def _chaos_leg(rng, *, n_shards=8, n_base=30, n_fault=12,
               min_delay_s=0.3):
    """Tail-tolerance leg (docs/robustness.md "Tail-tolerant fan-out"):
    3 real server nodes with the two replicas dialed through
    ChaosProxies (utils/netchaos.py — REAL sockets, not failpoints),
    read-routing pinned to primary so the straggler keeps being
    targeted, hedge-delay-ms fixed at 40.  Measures intersect/TopN
    latency three ways on identical data: no fault (baseline), one
    replica's responses delayed >= 5x the baseline p99 with hedging ON,
    and the same straggler with hedging OFF.  Asserts all three runs
    answer byte-identically; the hedged-vs-baseline p99 ratio is the
    headline number."""
    import http.client
    import socket
    import tempfile

    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.server import Config, Server
    from pilosa_tpu.utils.netchaos import ChaosProxy

    socks = []
    for _ in range(3):
        s = socket.socket()
        s.bind(("localhost", 0))
        socks.append(s)
    binds = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    proxies = {}
    hosts = [f"localhost:{binds[0]}"]
    for i in (1, 2):
        proxies[f"node{i}"] = ChaosProxy("localhost", binds[i])
        hosts.append(proxies[f"node{i}"].address)
    servers = []

    def post(port, path, body: bytes, timeout=600):
        conn = http.client.HTTPConnection("localhost", port,
                                          timeout=timeout)
        conn.request("POST", path, body=body)
        resp = conn.getresponse()
        data = resp.read()
        conn.close()
        if resp.status != 200:
            raise RuntimeError(f"{path}: {resp.status} {data[:200]!r}")
        return json.loads(data)

    try:
        for i, p in enumerate(binds):
            srv = Server(Config(
                data_dir=tempfile.mkdtemp(prefix=f"ptpu_chaos_{i}_"),
                bind=f"localhost:{p}", node_id=f"node{i}",
                cluster_hosts=hosts, replica_n=2,
                anti_entropy_interval=0,
                read_routing="primary", hedge_delay_ms=40.0))
            servers.append(srv)
            srv.open()
        coord = servers[0].cluster
        # an index whose placement gives node0 some — but not all —
        # replica sets, so a remote straggler actually owns primaries
        def remote_owned(name):
            return [s for s in range(n_shards)
                    if "node0" not in
                    coord.placement.shard_nodes(name, s)]
        index = next(name for name in (f"chaos{i}" for i in range(64))
                     if 0 < len(remote_owned(name)) < n_shards)
        p0 = binds[0]
        post(p0, f"/index/{index}", b"{}")
        post(p0, f"/index/{index}/field/a", b"{}")
        cols = np.unique(rng.integers(0, n_shards * SHARD_WIDTH,
                                      size=5000))
        rows = rng.integers(0, 8, size=cols.size)
        post(p0, f"/index/{index}/field/a/import", json.dumps({
            "rowIDs": rows.tolist(), "columnIDs": cols.tolist()}).encode())
        corpus = ["Count(Intersect(Row(a=1), Row(a=2)))",
                  "TopN(a, n=0)", "Count(Row(a=3))", "Row(a=4)"]
        for q in corpus:  # compile warm-up
            post(p0, f"/index/{index}/query", q.encode(), timeout=1800)
        # primary-policy target of a node0-less shard = first owner in
        # placement order (every node is READY here)
        straggler = coord.placement.shard_nodes(
            index, remote_owned(index)[0])[0]

        def run(n):
            lats, answers = [], []
            for i in range(n):
                q = corpus[i % len(corpus)]
                t0 = time.perf_counter()
                out = post(p0, f"/index/{index}/query", q.encode())
                lats.append(time.perf_counter() - t0)
                if i < len(corpus):
                    answers.append(out["results"])
            lats.sort()
            return lats[max(int(len(lats) * 0.99) - 1, 0)], answers

        p99_base, ans_base = run(n_base)
        delay = max(min_delay_s, 5 * p99_base)
        counts0 = servers[0].api.stats.snapshot()["counts"]
        hedges0 = counts0.get("cluster.hedges", 0)
        proxies[straggler].configure(f"down=latency:{delay}")
        p99_hedged, ans_hedged = run(n_fault)
        coord.hedge_reads = False
        p99_unhedged, ans_unhedged = run(n_fault)
        coord.hedge_reads = True
        proxies[straggler].heal()
        counts1 = servers[0].api.stats.snapshot()["counts"]
        assert ans_hedged == ans_base and ans_unhedged == ans_base, \
            "chaos leg answers diverged from the no-fault baseline"
        return {
            "answers_identical": True,
            "injected_delay_ms": round(delay * 1e3, 1),
            "p99_base_ms": round(p99_base * 1e3, 1),
            "p99_hedged_ms": round(p99_hedged * 1e3, 1),
            "p99_unhedged_ms": round(p99_unhedged * 1e3, 1),
            "hedged_vs_base": round(p99_hedged / p99_base, 2)
            if p99_base else None,
            "unhedged_vs_base": round(p99_unhedged / p99_base, 2)
            if p99_base else None,
            "hedges": counts1.get("cluster.hedges", 0) - hedges0,
            "hedge_wins": counts1.get("cluster.hedge_wins", 0)
            - counts0.get("cluster.hedge_wins", 0),
        }
    finally:
        for s in servers:
            try:
                s.close()
            # lint: allow(swallowed-exception) — bench teardown; the
            # server may already be down and the leg's numbers are in
            except Exception:
                pass
        for proxy in proxies.values():
            proxy.close()


def bench_chaos(rng):
    """Main-bench tail-tolerance leg: straggler p99 with hedging on vs
    off at full query counts (see _chaos_leg)."""
    return _chaos_leg(rng, n_base=40, n_fault=16)


def run_chaos_smoke(rng) -> dict:
    """Chaos leg of --smoke (docs/robustness.md): small query counts;
    asserts hedging actually fired and rescued the tail — hedged p99
    under the injected delay, unhedged p99 bound BY it — with answers
    byte-identical across all three runs (asserted in _chaos_leg)."""
    out = _chaos_leg(rng, n_base=20, n_fault=8, min_delay_s=0.3)
    assert out["hedges"] > 0, "straggler never triggered a hedge"
    assert out["p99_hedged_ms"] < out["injected_delay_ms"], out
    assert out["p99_unhedged_ms"] >= 0.8 * out["injected_delay_ms"], out
    assert out["p99_hedged_ms"] < out["p99_unhedged_ms"], out
    return out


def _slo_leg(rng, *, n_shards=6, fault_delay_s=0.5, overhead_q=100,
             overhead_runs=2):
    """SLO/alerting leg (docs/observability.md "SLOs & alerting"), two
    stories on real sockets.  (1) Alerting: a 3-node cluster with the
    replica nodes dialed through ChaosProxies; delaying every remote
    read past the 250 ms latency objective must fire slo-latency-burn
    within 2 evaluation passes of the first faulted sample, the on-fire
    hook must land a readable flight-recorder bundle inside the disk
    budget, and healing the proxies must resolve the alert.  The
    monitor cadence is parked at 60 s and the leg drives force-samples
    + evaluations itself, so "evaluation interval" is deterministic
    wall-clock-free.  (2) Overhead: the same workload against an
    evaluation-on vs evaluation-off single node (alert-rules=all vs
    off; the time-series sampler runs in BOTH, isolating evaluation
    cost) — evaluation rides the monitor thread, never a query, so
    serving qps must be noise-identical (the >=0.95x acceptance,
    best-of-N) with byte-identical answers."""
    import http.client
    import socket
    import tempfile

    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.server import Config, Server
    from pilosa_tpu.utils.netchaos import ChaosProxy

    def free_ports(n):
        socks = []
        for _ in range(n):
            s = socket.socket()
            s.bind(("localhost", 0))
            socks.append(s)
        ports = [s.getsockname()[1] for s in socks]
        for s in socks:
            s.close()
        return ports

    def post(port, path, body: bytes, timeout=600):
        conn = http.client.HTTPConnection("localhost", port,
                                          timeout=timeout)
        conn.request("POST", path, body=body)
        resp = conn.getresponse()
        data = resp.read()
        conn.close()
        if resp.status != 200:
            raise RuntimeError(f"{path}: {resp.status} {data[:200]!r}")
        return json.loads(data)

    out = {}

    # -- story 1: straggler -> fire -> bundle -> heal -> resolve ---------
    binds = free_ports(3)
    proxies = {}
    hosts = [f"localhost:{binds[0]}"]
    for i in (1, 2):
        proxies[f"node{i}"] = ChaosProxy("localhost", binds[i])
        hosts.append(proxies[f"node{i}"].address)
    servers = []
    try:
        for i, p in enumerate(binds):
            srv = Server(Config(
                data_dir=tempfile.mkdtemp(prefix=f"ptpu_slo_{i}_"),
                bind=f"localhost:{p}", node_id=f"node{i}",
                cluster_hosts=hosts, replica_n=1,
                anti_entropy_interval=0, read_routing="primary",
                hedge_reads=False,
                slo_latency_ms=250.0, slo_target=0.999,
                flight_recorder_mb=4,
                timeseries_interval=60, timeseries_window=1200,
                trace_sample_rate=0.0))
            servers.append(srv)
            srv.open()
        srv0 = servers[0]
        p0 = binds[0]
        coord = srv0.cluster
        # an index whose placement leaves node0 short of some shards, so
        # the proxy delay sits on the query path
        index = next(
            name for name in (f"slo{i}" for i in range(64))
            if any("node0" not in coord.placement.shard_nodes(name, s)
                   for s in range(n_shards)))
        post(p0, f"/index/{index}", b"{}")
        post(p0, f"/index/{index}/field/a", b"{}")
        cols = np.unique(rng.integers(0, n_shards * SHARD_WIDTH,
                                      size=3000))
        rows = rng.integers(0, 4, size=cols.size)
        post(p0, f"/index/{index}/field/a/import", json.dumps({
            "rowIDs": rows.tolist(), "columnIDs": cols.tolist()}).encode())
        q = "Count(Row(a=1))"
        baseline = post(p0, f"/index/{index}/query", q.encode(),
                        timeout=1800)["results"]
        eng = srv0.slo
        assert eng is not None and eng.enabled, "SLO engine absent"

        def pulse():
            for _ in range(3):
                assert post(p0, f"/index/{index}/query",
                            q.encode())["results"] == baseline, \
                    "answers diverged under the straggler"
            assert srv0.sample_timeseries(force=True)
            eng.evaluate()

        # prime one healthy sample so deltas span single intervals
        srv0.sample_timeseries(force=True)
        eng.evaluate()
        evals_before = eng.evaluations
        for proxy in proxies.values():
            proxy.configure(f"down=latency:{fault_delay_s}")
        for _ in range(3):
            pulse()
            if "slo-latency-burn" in eng.active:
                break
        fired = "slo-latency-burn" in eng.active
        evals_to_fire = (
            eng.active["slo-latency-burn"]["firedAtEvaluation"]
            - evals_before) if fired else None
        rec = srv0.flightrec
        bundle_ok, bundle_bytes = False, 0
        if rec is not None and rec.last is not None:
            with open(rec.last["path"]) as f:
                bundle = json.load(f)
            bundle_ok = "slo-latency-burn" in \
                (bundle.get("alerts") or {}).get("active", {})
            bundle_bytes = rec.last["bytes"]
        for proxy in proxies.values():
            proxy.heal()
        resolved = False
        for _ in range(10):
            pulse()
            if "slo-latency-burn" not in eng.active:
                resolved = True
                break
        out["alert"] = {
            "fired": fired,
            "evals_to_fire": evals_to_fire,
            "resolved": resolved,
            "bundle_ok": bundle_ok,
            "bundle_kb": round(bundle_bytes / 1024, 1),
            "budget_held": rec is not None
            and rec.disk_bytes() <= rec.budget_mb << 20,
            "fired_total": eng.fired_total,
            "resolved_total": eng.resolved_total,
        }
    finally:
        for s in servers:
            try:
                s.close()
            # lint: allow(swallowed-exception) — bench teardown; the
            # server may already be down and the leg's numbers are in
            except Exception:
                pass
        for proxy in proxies.values():
            proxy.close()

    # -- story 2: evaluation overhead on the serving path ----------------
    cols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, size=4000))
    rows = rng.integers(0, 4, size=cols.size)
    corpus = ["Count(Row(a=1))", "Row(a=2)", "TopN(a, n=3)",
              "Count(Intersect(Row(a=0), Row(a=3)))"]
    qps, answers = {}, {}
    for mode in ("on", "off"):
        srv = Server(Config(
            data_dir=tempfile.mkdtemp(prefix=f"ptpu_slo_{mode}_"),
            bind="localhost:0",
            alert_rules="all" if mode == "on" else "off",
            timeseries_interval=0.05, timeseries_window=30,
            trace_sample_rate=0.0))
        srv.open()
        try:
            p = srv.port
            post(p, "/index/ov", b"{}")
            post(p, "/index/ov/field/a", b"{}")
            post(p, "/index/ov/field/a/import", json.dumps({
                "rowIDs": rows.tolist(),
                "columnIDs": cols.tolist()}).encode())
            for qq in corpus:  # compile warm-up
                post(p, "/index/ov/query", qq.encode(), timeout=1800)
            best, got = 0.0, []
            for _ in range(overhead_runs):  # best-of-N: absorb CI noise
                t0 = time.perf_counter()
                got = []
                for i in range(overhead_q):
                    r = post(p, "/index/ov/query",
                             corpus[i % len(corpus)].encode())
                    if i < len(corpus):
                        got.append(r["results"])
                best = max(best,
                           overhead_q / (time.perf_counter() - t0))
            qps[mode] = best
            answers[mode] = got
            if mode == "on":
                assert srv.slo is not None \
                    and srv.slo.evaluations > 0, \
                    "evaluation-on leg never evaluated"
                out["evaluations_on"] = srv.slo.evaluations
            else:
                assert srv.slo is None, "alert-rules=off still built"
        finally:
            srv.close()
    out["answers_identical"] = answers["on"] == answers["off"]
    out["qps_on"] = round(qps["on"], 1)
    out["qps_off"] = round(qps["off"], 1)
    out["qps_ratio"] = round(qps["on"] / max(qps["off"], 1e-9), 3)
    return out


def bench_slo(rng):
    """Main-bench SLO/alerting leg: the same two stories at a larger
    overhead sample (see _slo_leg)."""
    return _slo_leg(rng, overhead_q=240, overhead_runs=3)


def run_slo_smoke(rng) -> dict:
    """SLO leg of --smoke (docs/observability.md "SLOs & alerting"):
    the straggler must page within 2 evaluation passes, the flight
    recorder must land a readable bundle inside its disk budget, the
    heal must resolve the alert, and burn-rate evaluation must be free
    on the serving path (>=0.95x qps, best-of-2) with byte-identical
    answers."""
    out = _slo_leg(rng)
    a = out["alert"]
    assert a["fired"] is True, a
    assert a["evals_to_fire"] <= 2, a
    assert a["bundle_ok"] is True and a["bundle_kb"] > 0, a
    assert a["budget_held"] is True, a
    assert a["resolved"] is True, a
    assert out["answers_identical"] is True, out
    assert out["qps_ratio"] >= 0.95, out
    return out


def _wire_leg(rng, *, waves=4, wave_q=48, threads=8, n_shards=4,
              dense_rows=6, dense_bits=320000, sparse_rows=6,
              sparse_run=3000, fallback_check=False):
    """Internal-wire leg (docs/cluster.md "Internal query wire"): 2 real
    server nodes where the coordinator (node0) owns NO shard of either
    bench index — "w1" and "qx" jump-hash every shard onto node1 — so
    every query is a pure remote fan-out and the internal wire carries
    all result traffic.  The SAME recorded corpus replays once over the
    PTPUQRY1 binary wire and once with every node pinned
    internal-wire=json (the PR 16 knob, flipped in-process between
    passes); answers are asserted byte-identical, and qps, wire
    bytes/query, and the per-wave wire-vs-reduce time split come off the
    cluster counters (cluster.wire_bytes_*, cluster.multi.wire_overhead
    / cluster.multi.reduce — same series both wires).

    Two corpora, matching the wire's two size regimes: a DENSE Row-heavy
    one ("w1": scattered random bits, segments ride raw or
    bitmap-packed; the JSON wire pays zlib+base64 of every 128 KiB
    segment either way, so this is the qps headline) and a SPARSE
    clustered one ("qx": short runs, roaring-packs to a few hundred
    bytes; this is the bytes/query headline)."""
    import http.client
    import socket
    import tempfile
    import threading

    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.server import Config, Server

    socks = []
    for _ in range(2):
        s = socket.socket()
        s.bind(("localhost", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    hosts = [f"localhost:{p}" for p in ports]
    servers = []

    def post(port, path, body: bytes, timeout=600):
        conn = http.client.HTTPConnection("localhost", port,
                                          timeout=timeout)
        conn.request("POST", path, body=body)
        resp = conn.getresponse()
        data = resp.read()
        conn.close()
        if resp.status != 200:
            raise RuntimeError(f"{path}: {resp.status} {data[:200]!r}")
        return json.loads(data)

    def set_wire(mode):
        # flip the knob in-process between passes: the serving branch
        # keys off cluster.internal_wire, the dispatch side off
        # client.wire_mode; clear the per-peer latches so the new mode
        # starts from a clean negotiation state
        for srv in servers:
            srv.cluster.internal_wire = mode
            srv.cluster.client.wire_mode = mode
            srv.cluster.client._wire_down.clear()
            srv.cluster.client._peer_wire.clear()

    try:
        for i, p in enumerate(ports):
            srv = Server(Config(
                data_dir=tempfile.mkdtemp(prefix=f"ptpu_wire_{i}_"),
                bind=hosts[i], node_id=f"node{i}", cluster_hosts=hosts,
                replica_n=1, anti_entropy_interval=0,
                internal_wire="bin1"))
            servers.append(srv)
            srv.open()
        p0 = ports[0]
        span = n_shards * SHARD_WIDTH
        for name in ("w1", "qx"):
            post(p0, f"/index/{name}", b"{}")
            post(p0, f"/index/{name}/field/a", b"{}")
        # seed through the coordinator's api IN-PROCESS (the public
        # import JSON adds nothing here); the cluster import fan-out
        # still routes each shard batch to its owner.  Dense rows are
        # scattered at ~dense_bits/n_shards bits per segment — dense
        # enough that the JSON wire's per-segment zlib actually costs
        # what it costs in production, which is the regime the binary
        # wire exists for.
        for r in range(dense_rows):
            cols = np.unique(rng.integers(0, span, size=dense_bits))
            servers[0].api.import_bits(
                "w1", "a", [r] * cols.size, cols.tolist())
        for r in range(sparse_rows):
            # short runs near the base of each shard: roaring run/array
            # containers, a few hundred wire bytes per packed segment
            cols = np.concatenate([
                np.arange(s * SHARD_WIDTH + r * sparse_run,
                          s * SHARD_WIDTH + (r + 1) * sparse_run)
                for s in range(n_shards)])
            servers[0].api.import_bits(
                "qx", "a", [r] * cols.size, cols.tolist())

        def gen_dense():
            a = int(rng.integers(0, dense_rows))
            b = (a + 1 + int(rng.integers(0, dense_rows - 1))) \
                % dense_rows
            kind = int(rng.integers(0, 3))
            if kind == 0:
                q = f"Row(a={a})Row(a={b})"
            elif kind == 1:
                q = f"Union(Row(a={a}), Row(a={b}))Count(Row(a={a}))"
            else:
                q = f"Row(a={a})Intersect(Row(a={a}), Row(a={b}))"
            return "w1", q

        def gen_sparse():
            a = int(rng.integers(0, sparse_rows))
            b = (a + 1) % sparse_rows
            return "qx", f"Row(a={a})Row(a={b})"

        dense_corpus = [gen_dense() for _ in range(wave_q)]
        sparse_corpus = [gen_sparse() for _ in range(wave_q)]
        stats = servers[0].stats

        def counters():
            return {
                "bytes": stats.count_value("cluster.wire_bytes_tx")
                + stats.count_value("cluster.wire_bytes_rx"),
                "frames": stats.count_value("cluster.wire_frames"),
                "fallback": stats.count_value("cluster.wire_fallback"),
                "wire_s": stats.timing_totals(
                    "cluster.multi.wire_overhead")[1],
                "reduce_s": stats.timing_totals(
                    "cluster.multi.reduce")[1],
            }

        # replay: recorded corpus, threaded like production fan-in, but
        # dispatched through the coordinator's api.query IN-PROCESS —
        # the public HTTP+JSON surface is identical in both modes and
        # would dilute the internal-wire signal this leg exists to
        # measure.  Two passes: an UNTIMED identity pass that captures
        # every answer in public wire form (result_to_wire — exactly
        # what a client would see, for the byte-identity assert), then
        # the timed pass, pure dispatch with results consumed but not
        # re-serialized.  Returns qps + answers + the counter deltas of
        # the timed window.
        from pilosa_tpu.parallel.cluster import result_to_wire

        def replay(corpus, n):
            answers = {}
            for i, (idx, q) in enumerate(corpus):
                res = servers[0].api.query(idx, q)
                answers[i] = json.dumps(
                    [result_to_wire(r) for r in res], sort_keys=True)

            def post_one(item):
                _i, (idx, q) = item
                servers[0].api.query(idx, q)

            items = [(i, corpus[i % len(corpus)]) for i in range(n)]
            c0 = counters()
            t0 = time.perf_counter()
            with ThreadPoolExecutor(threads) as pool:
                list(pool.map(post_one, items))
            dt = time.perf_counter() - t0
            c1 = counters()
            d = {k: c1[k] - c0[k] for k in c0}
            return {
                "qps": n / dt,
                "answers": answers,
                "bytes_per_q": d["bytes"] / n,
                "frames_per_q": d["frames"] / n,
                "fallback": d["fallback"],
                "wire_ms_per_q": d["wire_s"] / n * 1e3,
                "reduce_ms_per_q": d["reduce_s"] / n * 1e3,
            }

        runs = {}
        for mode in ("bin1", "json"):
            set_wire(mode)
            for idx, q in dense_corpus[:4] + sparse_corpus[:4]:
                servers[0].api.query(idx, q)  # warm compiles + wire
            runs[mode] = {
                "dense": replay(dense_corpus, waves * wave_q),
                "sparse": replay(sparse_corpus, wave_q),
            }
        for leg in ("dense", "sparse"):
            assert runs["bin1"][leg]["answers"] == \
                runs["json"][leg]["answers"], \
                f"binary wire diverged from JSON answers ({leg})"

        out = {
            "answers_identical": True,
            "qps_bin1": round(runs["bin1"]["dense"]["qps"], 1),
            "qps_json": round(runs["json"]["dense"]["qps"], 1),
            "bin1_vs_json": round(runs["bin1"]["dense"]["qps"]
                                  / runs["json"]["dense"]["qps"], 2),
            "dense_wire_bytes_per_q": {
                m: int(runs[m]["dense"]["bytes_per_q"])
                for m in runs},
            "sparse_wire_bytes_per_q": {
                m: int(runs[m]["sparse"]["bytes_per_q"])
                for m in runs},
            "sparse_bytes_ratio": round(
                runs["json"]["sparse"]["bytes_per_q"]
                / runs["bin1"]["sparse"]["bytes_per_q"], 2),
            "wire_ms_per_q": {
                m: round(runs[m]["dense"]["wire_ms_per_q"], 3)
                for m in runs},
            "reduce_ms_per_q": {
                m: round(runs[m]["dense"]["reduce_ms_per_q"], 3)
                for m in runs},
            "frames_per_q_bin1": round(
                runs["bin1"]["dense"]["frames_per_q"], 1),
        }
        if fallback_check:
            # mixed-version exercise: node1 pinned json, node0 still
            # binary and force-marked optimistic — the first POST must
            # 415, downgrade-latch, retry as JSON, and answer
            # identically
            servers[1].cluster.internal_wire = "json"
            cl0 = servers[0].cluster
            cl0.internal_wire = "bin1"
            cl0.client.wire_mode = "bin1"
            cl0.client._wire_down.clear()
            host1 = cl0.nodes[1].host
            cl0.client._peer_wire[host1] = "bin1"
            fb0 = stats.count_value("cluster.wire_fallback")
            idx, q = sparse_corpus[0]
            res = servers[0].api.query(idx, q)
            got = json.dumps([result_to_wire(r) for r in res],
                             sort_keys=True)
            fb = stats.count_value("cluster.wire_fallback") - fb0
            assert fb >= 1, "415 downgrade never fired"
            assert got == runs["bin1"]["sparse"]["answers"][0], \
                "downgraded answer diverged"
            out["fallback"] = {"count": int(fb),
                               "answers_identical": True}
        return out
    finally:
        for s in servers:
            try:
                s.close()
            # lint: allow(swallowed-exception) — bench teardown; the
            # server may already be down and the leg's numbers are in
            except Exception:
                pass


def bench_wire(rng):
    """Main-bench internal-wire leg: binary vs JSON at full wave counts
    on the recorded dense + sparse corpora (see _wire_leg)."""
    return _wire_leg(rng, waves=5, wave_q=48, threads=8)


def run_wire_smoke(rng) -> dict:
    """Wire leg of --smoke (docs/cluster.md "Internal query wire"):
    small corpus; asserts answers byte-identical across wires, sparse
    wire bytes/query actually reduced by the roaring framing, and the
    mixed-version 415 downgrade exercised end-to-end."""
    out = _wire_leg(rng, waves=2, wave_q=16, threads=6,
                    dense_rows=4, dense_bits=240000, sparse_run=1500,
                    fallback_check=True)
    assert out["sparse_bytes_ratio"] > 1.5, \
        f"binary wire did not shrink sparse results: {out}"
    assert out["fallback"]["count"] >= 1, out
    return out


def _tenant_leg(rng, *, n_polite=20, flood_threads=8, flood_iters=2000,
                n_shards=4):
    """Two-tenant flood leg (docs/robustness.md "Tenant isolation"): a
    hostile tenant hammers the query gate from ``flood_threads`` threads
    that never honor Retry-After, while a polite tenant runs its fixed
    corpus sequentially with bounded, Retry-After-honoring retries.
    Three passes on identical data: polite alone (idle baseline), the
    flood with isolation ON (weighted-fair DRR, polite:4 hostile:1),
    and the flood with isolation OFF (the legacy single FIFO).  Records
    polite p99 per pass, per-tenant shed counts + attribution from the
    tenant registry, and hedge-budget denials; asserts the polite
    corpus answers byte-identically across all three passes — the
    isolation plane must never change WHAT an admitted query returns,
    only WHEN it runs."""
    import http.client
    import tempfile
    import threading

    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.server import Config, Server
    from pilosa_tpu.utils import tenant as qtenant

    cols = np.unique(rng.integers(0, n_shards * SHARD_WIDTH, size=8000))
    rows = rng.integers(0, 8, size=cols.size)
    corpus = ["Count(Intersect(Row(f=1), Row(f=2)))",
              "TopN(f, n=0)", "Count(Row(f=3))", "Row(f=4)"]

    def post(port, path, body, tenant=None, timeout=600):
        conn = http.client.HTTPConnection("localhost", port,
                                          timeout=timeout)
        headers = {qtenant.TENANT_HEADER: tenant} if tenant else {}
        conn.request("POST", path, body=body, headers=headers)
        resp = conn.getresponse()
        data = resp.read()
        ra = resp.getheader("Retry-After")
        conn.close()
        return resp.status, (float(ra) if ra else None), data

    def run_pass(isolation):
        srv = Server(Config(
            data_dir=tempfile.mkdtemp(prefix="ptpu_tenant_"),
            bind="localhost:0", anti_entropy_interval=0,
            max_queries=2, queue_timeout=0.2,
            tenant_isolation=isolation,
            tenant_weights="polite:4,hostile:1"))
        srv.open()
        qtenant.REGISTRY.clear()
        try:
            p = srv.port
            st, _, _ = post(p, "/index/t", b"{}")
            assert st == 200
            st, _, _ = post(p, "/index/t/field/f", b"{}")
            assert st == 200
            st, _, _ = post(p, "/index/t/field/f/import", json.dumps({
                "rowIDs": rows.tolist(),
                "columnIDs": cols.tolist()}).encode())
            assert st == 200
            for q in corpus:  # compile warm-up
                st, _, _ = post(p, "/index/t/query", q.encode(),
                                tenant="polite", timeout=1800)
                assert st == 200

            def polite_run(n):
                lats, answers, sheds = [], [], 0
                for i in range(n):
                    q = corpus[i % len(corpus)]
                    t0 = time.perf_counter()
                    for _ in range(40):
                        st, ra, data = post(p, "/index/t/query",
                                            q.encode(), tenant="polite")
                        if st == 200:
                            break
                        assert st == 503, (st, data[:200])
                        sheds += 1
                        time.sleep(min(ra or 0.05, 0.25))
                    else:
                        raise RuntimeError(
                            "polite query never admitted in 40 tries")
                    # per-query wall time INCLUDES any shed+retry waits:
                    # the polite tenant's experienced latency, not the
                    # admitted attempt's
                    lats.append(time.perf_counter() - t0)
                    if i < len(corpus):
                        answers.append(json.loads(data)["results"])
                lats.sort()
                return (lats[max(int(len(lats) * 0.99) - 1, 0)],
                        answers, sheds)

            p99_idle, ans_idle, idle_sheds = polite_run(n_polite)
            assert idle_sheds == 0, "idle polite pass was shed?"

            stop = threading.Event()

            def flood():
                for _ in range(flood_iters):
                    if stop.is_set():
                        return
                    # rude by design: a 503's Retry-After is ignored
                    post(p, "/index/t/query", corpus[0].encode(),
                         tenant="hostile")

            threads = [threading.Thread(target=flood, daemon=True)
                       for _ in range(flood_threads)]
            for t in threads:
                t.start()
            time.sleep(0.2)  # let the flood fill the slots + queue
            try:
                p99_flood, ans_flood, polite_sheds = polite_run(n_polite)
            finally:
                stop.set()
                for t in threads:
                    t.join()
            assert ans_flood == ans_idle, \
                "admitted answers diverged under the flood"
            reg = qtenant.REGISTRY.snapshot()
            hostile_shed = reg.get("hostile", {}).get("shed", 0)
            total_shed = hostile_shed + \
                reg.get("polite", {}).get("shed", 0)
            return {
                "fair": srv.admission.snapshot()["fair"],
                "p99_idle_ms": round(p99_idle * 1e3, 1),
                "p99_flood_ms": round(p99_flood * 1e3, 1),
                "polite_vs_idle": round(p99_flood / p99_idle, 2)
                if p99_idle else None,
                "polite_sheds": polite_sheds,
                "hostile_sheds": hostile_shed,
                "total_sheds": total_shed,
                "shed_attribution": round(hostile_shed / total_shed, 3)
                if total_shed else None,
                "hedge_denied": reg.get("polite", {}).get(
                    "hedgeDenied", 0) + reg.get("hostile", {}).get(
                    "hedgeDenied", 0),
            }, ans_idle
        finally:
            qtenant.REGISTRY.clear()
            try:
                srv.close()
            # lint: allow(swallowed-exception) — bench teardown; the
            # pass's numbers are already collected
            except Exception:
                pass

    on, ans_on = run_pass(True)
    off, ans_off = run_pass(False)
    return {
        # the isolation plane changes scheduling, never answers
        "answers_identical": ans_on == ans_off,
        "isolation_on": on,
        "isolation_off": off,
    }


def bench_tenant(rng):
    """Main-bench tenant-isolation leg: polite-tenant p99 under a
    hostile flood, weighted-fair admission on vs off (see _tenant_leg).
    The acceptance read on real hardware: isolation ON holds polite p99
    within ~1.5x its idle baseline while isolation OFF degrades with
    the flood."""
    return _tenant_leg(rng, n_polite=40, flood_threads=8)


def run_tenant_smoke(rng) -> dict:
    """Tenant leg of --smoke (docs/robustness.md "Tenant isolation"):
    small counts; asserts the flood's sheds land on the hostile tenant
    (>=95% attribution), the polite tenant is never shed under
    isolation, and admitted answers are byte-identical across idle /
    isolation-on / isolation-off passes (asserted in _tenant_leg).  The
    1.5x polite-p99 bound is recorded, not asserted — CPU-smoke timing
    is too noisy to judge it; the bench on real hardware does."""
    out = _tenant_leg(rng, n_polite=12, flood_threads=6,
                      flood_iters=1000)
    on = out["isolation_on"]
    assert out["answers_identical"] is True, out
    assert on["fair"] is True and out["isolation_off"]["fair"] is False
    assert on["total_sheds"] > 0, f"flood never shed: {out}"
    assert on["shed_attribution"] >= 0.95, out
    assert on["polite_sheds"] == 0, out
    return out


# -- numpy oracle baselines (single-thread reference-algorithm stand-in) ----

def _np_frag(holder, index, field, view=None):
    f = holder.field(index, field)
    v = f.view(view or "standard")
    return {s: fr.words for s, fr in v.fragments.items()}


def cpu_config1(holder, meta, rng, n=64):
    frag = _np_frag(holder, "startrace", "stargazer")[0]
    rows = rng.integers(0, meta["star_rows"], size=n)
    t0 = time.perf_counter()
    for r in rows:
        int(np.bitwise_count(frag[r]).sum())
    return n / (time.perf_counter() - t0)


def cpu_config2(holder, meta, rng, n=64):
    frag = _np_frag(holder, "startrace", "stargazer")[0]
    sets = _rand_rows(rng, meta["star_rows"], n)
    t0 = time.perf_counter()
    for q in sets:
        seg = frag[q[0]]
        for i in range(1, 8):
            seg = seg & frag[q[i]]
        int(np.bitwise_count(seg).sum())
    return n / (time.perf_counter() - t0)


def cpu_config3(holder, meta, rng, n=2):
    lang = _np_frag(holder, "lang10m", "language")
    stars = _np_frag(holder, "lang10m", "stars")
    rs = rng.integers(0, 16, size=n)
    t0 = time.perf_counter()
    for r in rs:
        counts = np.zeros(64, dtype=np.int64)
        for s, frag in lang.items():
            filt = stars[s][r]
            masked = frag & filt[None, :]
            c = np.bitwise_count(masked).sum(axis=1).astype(np.int64)
            counts[: c.size] += c
        nz = np.nonzero(counts)[0]
        sorted(((int(counts[i]), -int(i)) for i in nz), reverse=True)[:50]
    return n / (time.perf_counter() - t0)


def cpu_config4(holder, meta, rng, n=2):
    """Bit-sliced range+sum scan with numpy words — the reference's BSI
    algorithm (fragment.go:1111 sum, :1436 rangeGT) on dense words."""
    frags = _np_frag(holder, "bsi64", "v", "bsig_v")
    xs = rng.integers(0, 1_000_000, size=n)
    t0 = time.perf_counter()
    for x in xs:
        total = 0
        for s, w in frags.items():
            depth = w.shape[0] - 2
            exists = w[0]
            # rangeGT via MSB-first magnitude compare
            eq = exists.copy()
            gt = np.zeros_like(exists)
            for i in range(depth - 1, -1, -1):
                bit = w[2 + i]
                if (int(x) >> i) & 1:
                    eq &= bit
                else:
                    gt |= eq & bit
                    eq &= ~bit
            filt = gt
            for i in range(depth):
                total += int(np.bitwise_count(w[2 + i] & filt).sum()) << i
    return n / (time.perf_counter() - t0)


def bench_http(server_port, rng, n_rows):
    """Config 2 through the real HTTP surface: concurrent POSTs over
    per-thread keep-alive connections (the ThreadingHTTPServer overlaps
    request threads the same way the engine bench overlaps client
    threads)."""
    import http.client
    import threading

    B, n_batches, T = 256, 24, 8
    local = threading.local()

    def post(body):
        conn = getattr(local, "conn", None)
        if conn is None:
            conn = local.conn = http.client.HTTPConnection(
                "localhost", server_port, timeout=120)
        try:
            conn.request("POST", "/index/startrace/query",
                         body=body.encode())
            resp = conn.getresponse()
            data = resp.read()
        except (OSError, http.client.HTTPException):
            conn.close()
            local.conn = None
            raise
        assert resp.status == 200, data
        return data

    def batch():
        sets = _rand_rows(rng, n_rows, B)
        return " ".join("Count(Intersect(" + ", ".join(
            f"Row(stargazer={r})" for r in q) + "))" for q in sets)

    post(batch())  # warm
    batches = [batch() for _ in range(n_batches)]
    t0 = time.perf_counter()
    with ThreadPoolExecutor(T) as pool:
        list(pool.map(post, batches))
    return (B * n_batches) / (time.perf_counter() - t0)


def _http_count_load(port, index, field, n_rows, rng, threads,
                     per_thread=120):
    """Drive ``threads`` keep-alive clients of SINGLE small Count queries
    (one query per POST — the serving shape cross-query dynamic batching
    exists for; distinct literals keep the result cache out of it).
    Returns (qps, p50_s)."""
    import http.client
    import threading

    local = threading.local()

    def post(body: bytes):
        conn = getattr(local, "conn", None)
        if conn is None:
            conn = local.conn = http.client.HTTPConnection(
                "localhost", port, timeout=120)
        try:
            conn.request("POST", f"/index/{index}/query", body=body)
            resp = conn.getresponse()
            data = resp.read()
        except (OSError, http.client.HTTPException):
            conn.close()
            local.conn = None
            raise
        assert resp.status == 200, data
        return data

    rows = rng.integers(0, n_rows, size=threads * per_thread)
    lats: list[float] = []
    lock = threading.Lock()

    def worker(k):
        mine = []
        for i in range(k * per_thread, (k + 1) * per_thread):
            t1 = time.perf_counter()
            post(f"Count(Row({field}={rows[i]}))".encode())
            mine.append(time.perf_counter() - t1)
        with lock:
            lats.extend(mine)

    t0 = time.perf_counter()
    ts = [threading.Thread(target=worker, args=(k,))
          for k in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    dt = time.perf_counter() - t0
    return threads * per_thread / dt, float(np.median(lats))


def bench_http_dynamic_batching(holder, executor, meta, rng):
    """Concurrent-HTTP dynamic-batching config (docs/batching.md): 16
    client threads of small single-Count queries through the REAL server,
    ``dispatch-batch`` on vs off, plus single-client p50 both ways (the
    acceptance criteria: >=4x qps at 16 threads, solo p50 within 10%).
    Reports the on-server's batch-size histogram and window-wait
    percentiles from /debug/vars."""
    import tempfile
    import urllib.request

    from pilosa_tpu.executor import Executor as _Ex
    from pilosa_tpu.server import Config, Server

    n_rows = meta["star_rows"]
    out = {}
    for mode, ex in (("on", executor),
                     ("off", _Ex(holder, use_mesh=True,
                                 dispatch_batch=False))):
        srv = Server(Config(
            data_dir=tempfile.mkdtemp(prefix=f"ptpu_dynb_{mode}_"),
            bind="localhost:0", anti_entropy_interval=0,
            dispatch_batch=(mode == "on")))
        try:
            srv.holder.indexes = holder.indexes
            srv.api.holder = holder
            srv.api.executor = ex
            srv.open()
            # warm: compile the padded fused query-axis shapes before
            # the timed window so XLA compiles don't pollute it
            _http_count_load(srv.port, "startrace", "stargazer", n_rows,
                             rng, 16, per_thread=20)
            (qps, _), spread = best_of(lambda: _http_count_load(
                srv.port, "startrace", "stargazer", n_rows, rng, 16))
            (solo_qps, solo_p50), _ = best_of(lambda: _http_count_load(
                srv.port, "startrace", "stargazer", n_rows, rng, 1,
                per_thread=64))
            out[f"qps_{mode}"] = round(qps, 1)
            out[f"spread_{mode}"] = spread
            out[f"solo_p50_ms_{mode}"] = round(solo_p50 * 1e3, 3)
            if mode == "on":
                with urllib.request.urlopen(
                        f"http://localhost:{srv.port}/debug/vars",
                        timeout=30) as resp:
                    snap = json.loads(resp.read())
                b = snap.get("dispatchBatcher", {})
                out["batch_size_hist"] = b.get("batchSize")
                out["window_wait"] = b.get("windowWaitS")
                out["fused_launches"] = b.get("fusedLaunches")
        finally:
            srv.httpd.shutdown()
            if mode == "off":
                ex.close()
    out["speedup"] = round(out["qps_on"] / out["qps_off"], 2) \
        if out.get("qps_off") else None
    return out


def run_http_batch_smoke(rng) -> dict:
    """Dynamic-batching leg of --smoke (docs/batching.md): 16 concurrent
    HTTP clients of small single-Count queries against a real server with
    ``dispatch-batch`` on, then off — asserting the on-mode actually
    fused launches and both modes agree on a sample answer.  The >=4x
    qps acceptance floor is a device-dispatch-floor effect and is judged
    by the full bench on real hardware, not this CPU smoke."""
    import tempfile
    import urllib.request

    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.server.server import Config, Server

    out = {}
    want = None
    # one dataset for BOTH modes (the rng advances per draw — sampling
    # inside the loop would hand each server different bits and void the
    # answer comparison)
    cols = rng.integers(0, SHARD_WIDTH, size=20_000)
    rws = rng.integers(0, 64, size=20_000)
    for mode in ("on", "off"):
        srv = Server(Config(
            data_dir=tempfile.mkdtemp(prefix=f"ptpu_smkb_{mode}_"),
            bind="localhost:0", anti_entropy_interval=0,
            dispatch_batch=(mode == "on"),
            dispatch_batch_window_us=1000))
        try:
            srv.open()

            def post(path, body):
                req = urllib.request.Request(
                    f"http://localhost:{srv.port}{path}", method="POST",
                    data=body.encode())
                with urllib.request.urlopen(req, timeout=60) as resp:
                    return resp.read()

            post("/index/dynb", "{}")
            post("/index/dynb/field/f", "{}")
            post("/index/dynb/field/f/import", json.dumps(
                {"rowIDs": rws.tolist(), "columnIDs": cols.tolist()}))
            got = json.loads(post("/index/dynb/query",
                                  "Count(Row(f=7))"))["results"]
            if want is None:
                want = got
            assert got == want, f"batched answer diverged: {got} != {want}"
            _http_count_load(srv.port, "dynb", "f", 64, rng, 16,
                             per_thread=8)  # warm compiles
            qps, p50 = _http_count_load(srv.port, "dynb", "f", 64, rng,
                                        16, per_thread=32)
            out[f"qps_{mode}"] = round(qps, 1)
            out[f"p50_ms_{mode}"] = round(p50 * 1e3, 2)
            if mode == "on":
                with urllib.request.urlopen(
                        f"http://localhost:{srv.port}/debug/vars",
                        timeout=30) as resp:
                    snap = json.loads(resp.read())
                b = snap["dispatchBatcher"]
                assert b["fusedLaunches"] > 0, \
                    "16 concurrent clients never produced a fused launch"
                out["fused_launches"] = b["fusedLaunches"]
                out["batch_size_hist"] = b["batchSize"]
                out["window_wait"] = b["windowWaitS"]
                out["client_aborts"] = snap["counts"].get(
                    "http.client_abort", 0)
        finally:
            srv.close()
    out["speedup"] = round(out["qps_on"] / out["qps_off"], 2)
    return out


def run_observability_smoke(rng, baseline_qps=None) -> dict:
    """Observability leg of --smoke (docs/observability.md): with
    tracing, latency histograms, and the slow-query log all armed, the
    profile-OFF serving path must stay within noise of the PR 4 batching
    leg (< 5%: collection is a contextvar read and a histogram bucket
    increment per stage), and ``?profile=true`` must return a populated
    stage tree whose trace id resolves at /debug/traces."""
    import tempfile
    import urllib.request

    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.server.server import Config, Server

    out = {}
    srv = Server(Config(
        data_dir=tempfile.mkdtemp(prefix="ptpu_smko_"),
        bind="localhost:0", anti_entropy_interval=0,
        dispatch_batch_window_us=1000,
        slow_query_threshold=0.5, trace_sample_rate=1.0,
        # fast time-series cadence so the leg can assert a full window
        # of samples in seconds instead of minutes
        timeseries_interval=0.05, timeseries_window=1.0))
    try:
        srv.open()

        def post(path, body):
            req = urllib.request.Request(
                f"http://localhost:{srv.port}{path}", method="POST",
                data=body.encode())
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.read()

        def get(path):
            with urllib.request.urlopen(
                    f"http://localhost:{srv.port}{path}",
                    timeout=30) as resp:
                return resp.read()

        cols = rng.integers(0, SHARD_WIDTH, size=20_000)
        rws = rng.integers(0, 64, size=20_000)
        post("/index/obs", "{}")
        post("/index/obs/field/f", "{}")
        post("/index/obs/field/f/import", json.dumps(
            {"rowIDs": rws.tolist(), "columnIDs": cols.tolist()}))
        # same load shape as the batching leg; best-of-2 after a warm
        # pass so a stray scheduler hiccup can't fail the 5% bound
        _http_count_load(srv.port, "obs", "f", 64, rng, 16, per_thread=8)
        qps = max(_http_count_load(srv.port, "obs", "f", 64, rng, 16,
                                   per_thread=32)[0]
                  for _ in range(2))
        out["qps"] = round(qps, 1)
        if baseline_qps:
            out["overhead_pct"] = round(
                100.0 * (1.0 - qps / baseline_qps), 1)
            assert qps >= 0.95 * baseline_qps, \
                (f"profile-off observability overhead over 5%: "
                 f"{qps:.0f} qps vs batching leg {baseline_qps:.0f}")
        # profile-on: a populated stage tree, inline with the response
        prof = json.loads(post("/index/obs/query?profile=true",
                               "Count(Row(f=7))"))
        assert prof.get("profile", {}).get("children"), \
            "?profile=true returned an empty stage tree"
        out["profile_stages"] = len(prof["profile"]["children"])
        tid = prof["traceID"]
        spans = json.loads(get(f"/debug/traces?trace={tid}"))["spans"]
        assert spans, "profile trace id unknown to /debug/traces"
        # slow-query log: drop the threshold and capture one.  The log
        # entry lands in the handler's post-response accounting, so poll
        # briefly instead of racing the microseconds after the reply.
        srv.slowlog.threshold_s = 1e-9
        post("/index/obs/query", "Count(Row(f=9))")
        slow_deadline = time.perf_counter() + 5
        while True:
            slow = json.loads(get("/debug/slow"))
            if slow["entries"] or time.perf_counter() >= slow_deadline:
                break
            time.sleep(0.02)
        assert slow["entries"], "slow-query log captured nothing"
        out["slow_recorded"] = slow["recorded"]
        # histograms: p99 derivable from the exposition
        text = get("/metrics").decode()
        assert "pilosa_tpu_http_query_seconds_bucket" in text, \
            "/metrics lacks the http.query latency histogram"
        # device runtime (docs/observability.md "Device runtime"): after
        # the load above the time-series ring must hold >= its window of
        # samples (wrapped at least once), and the compile registry must
        # have seen the leg's executables compile
        deadline = time.perf_counter() + 10
        while True:
            ts = json.loads(get("/debug/timeseries"))
            if (ts["coveredS"] >= ts["windowS"]
                    and ts["samplesTotal"] > ts["capacity"]) \
                    or time.perf_counter() >= deadline:
                break
            time.sleep(0.05)
        assert ts["coveredS"] >= ts["windowS"], \
            (f"time-series ring covers {ts['coveredS']}s of its "
             f"{ts['windowS']}s window after the load")
        assert ts["samplesTotal"] > ts["capacity"], \
            "time-series ring never wrapped"
        out["timeseries_samples"] = len(ts["samples"])
        dev = json.loads(get("/debug/vars"))["device"]
        assert dev["compiles"]["compiles"] > 0, \
            "compile registry saw no executable compile"
        assert "pilosa_tpu_device_compiles_total" in text and \
            "pilosa_tpu_device_padding_waste_ratio" in text and \
            "pilosa_tpu_device_decode_workspace_peak_bytes" in text, \
            "/metrics lacks the device-runtime families"
        out["device"] = {
            "compiles": dev["compiles"]["compiles"],
            "retraces": dev["compiles"]["retraces"],
            "compile_s": dev["compiles"]["compileSecondsTotal"],
            "padding_waste_ratio":
                dev["launches"]["paddingWasteRatio"],
        }
    finally:
        srv.close()
    return out


def _ingest_stream_load(port, index, field, rng, n_records,
                        n_rows=64, col_span=None, batch_records=50_000,
                        stop_evt=None):
    """Stream framed record batches at the binary ingest endpoint
    (docs/ingest.md) until ``n_records`` are acked (or until
    ``stop_evt`` is set, looping forever).  503s honor Retry-After and
    resend the batch.  Returns {records, bytes, seconds, retries}."""
    import http.client
    import threading
    import urllib.error
    import urllib.request

    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.ingest import wire

    span = col_span if col_span is not None else SHARD_WIDTH
    sent = sent_bytes = retries = 0
    t0 = time.perf_counter()
    while (stop_evt is not None and not stop_evt.is_set()) \
            or (stop_evt is None and sent < n_records):
        n = min(batch_records, max(n_records - sent, 1)) \
            if stop_evt is None else batch_records
        rows = rng.integers(0, n_rows, size=n)
        cols = rng.integers(0, span, size=n)
        body = wire.encode_records(rows, cols)
        while True:
            req = urllib.request.Request(
                f"http://localhost:{port}/index/{index}/field/{field}"
                f"/ingest", data=body, method="POST")
            req.add_header("Content-Type", "application/octet-stream")
            try:
                with urllib.request.urlopen(req, timeout=120) as resp:
                    resp.read()
                break
            except urllib.error.HTTPError as e:
                e.read()
                if e.code != 503:
                    raise
                retries += 1
                time.sleep(0.05)
            except (OSError, http.client.HTTPException):
                if stop_evt is not None and stop_evt.is_set():
                    break  # server shutting down under us
                raise
        sent += n
        sent_bytes += len(body)
    return {"records": sent, "bytes": sent_bytes,
            "seconds": time.perf_counter() - t0, "retries": retries}


def bench_ingest(holder, executor, meta, rng):
    """Streaming-ingest config (docs/ingest.md): sustained binary-frame
    ingest alone, then ingest CONCURRENT with the intersect8 read leg —
    the read-qps retention ratio is the read/write interference
    headline (ROADMAP item 4: reads should hold >=80% of idle qps)."""
    import tempfile
    import threading

    from pilosa_tpu.server import Config, Server

    B, n_batches, T = 4096, 8, 8
    n_rows = meta["star_rows"]

    def read_batch():
        sets = _rand_rows(rng, n_rows, B)
        return " ".join(
            "Count(Intersect(" + ", ".join(
                f"Row(stargazer={r})" for r in q) + "))"
            for q in sets)

    def read_run():
        batches = [read_batch() for _ in range(n_batches)]
        return _run_batches(executor, "startrace", batches, T)

    srv = Server(Config(data_dir=tempfile.mkdtemp(prefix="ptpu_bing_"),
                        bind="localhost:0", anti_entropy_interval=0))
    srv.holder.indexes = holder.indexes  # serve the bench data
    srv.api.holder = holder
    srv.committer.holder = holder
    srv.open()
    try:
        idx = holder.index("startrace")
        idx.create_field_if_not_exists("ingested")
        executor.execute("startrace", read_batch())  # warm
        (qps_idle, _b, _p), _sp = best_of(read_run, n=2)
        # sustained ingest alone
        alone = _ingest_stream_load(srv.port, "startrace", "ingested",
                                    rng, 2_000_000)
        # ingest concurrent with the read leg
        stop = threading.Event()
        conc: dict = {}
        t = threading.Thread(
            target=lambda: conc.update(_ingest_stream_load(
                srv.port, "startrace", "ingested", rng, 0,
                stop_evt=stop)))
        t.start()
        try:
            (qps_load, _b2, _p2), _sp2 = best_of(read_run, n=2)
        finally:
            stop.set()
            t.join(timeout=120)
        ing = srv.committer.snapshot()
        return {
            "ingest_records_per_s": round(
                alone["records"] / alone["seconds"], 1),
            "ingest_mb_per_s": round(
                alone["bytes"] / alone["seconds"] / 1e6, 2),
            "ingest_retries": alone["retries"] + conc.get("retries", 0),
            "concurrent_ingest_records_per_s": round(
                conc["records"] / conc["seconds"], 1)
            if conc.get("seconds") else 0.0,
            "read_qps_idle": round(qps_idle, 1),
            "read_qps_under_ingest": round(qps_load, 1),
            "read_qps_retention": round(qps_load / qps_idle, 3),
            "flushes": ing["flushes"],
            "delta_folds": ing["folds"],
        }
    finally:
        # NOT srv.close(): that would close the SHARED bench holder (the
        # same reason bench_http only shuts the listener down)
        srv.httpd.shutdown()
        if hasattr(srv.httpd, "close_connections"):
            srv.httpd.close_connections()
        srv.httpd.server_close()
        srv.committer.close()


def run_ingest_smoke(rng) -> dict:
    """Ingest leg of --smoke (docs/ingest.md): the same corpus through
    the binary streaming endpoint and through the JSON bulk import must
    answer identically — while the deltas are overlay-resident AND
    after the merge folds them — plus a small read-under-ingest
    retention measurement (the acceptance floor is judged on real
    hardware by the full bench, not this CPU smoke)."""
    import tempfile
    import threading
    import urllib.request

    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.server import Config, Server

    srv = Server(Config(data_dir=tempfile.mkdtemp(prefix="ptpu_smki_"),
                        bind="localhost:0", anti_entropy_interval=0,
                        ingest_flush_ms=20.0))
    srv.open()
    try:
        def post(path, body, ctype="application/json"):
            req = urllib.request.Request(
                f"http://localhost:{srv.port}{path}", method="POST",
                data=body if isinstance(body, bytes) else body.encode())
            req.add_header("Content-Type", ctype)
            with urllib.request.urlopen(req, timeout=120) as resp:
                return resp.read()

        post("/index/ings", "{}")
        for f in ("fb", "fi", "readf"):
            post(f"/index/ings/field/{f}", "{}")
        n = 120_000
        rows = rng.integers(0, 64, size=n)
        cols = rng.integers(0, 2 * SHARD_WIDTH, size=n)
        # read working set + its baseline qps
        post("/index/ings/field/readf/import", json.dumps(
            {"rowIDs": rows.tolist(), "columnIDs": cols.tolist()}))
        _http_count_load(srv.port, "ings", "readf", 64, rng, 8,
                         per_thread=8)  # warm compiles
        qps_idle, _ = _http_count_load(srv.port, "ings", "readf", 64,
                                       rng, 8, per_thread=24)
        # bulk twin
        post("/index/ings/field/fb/import", json.dumps(
            {"rowIDs": rows.tolist(), "columnIDs": cols.tolist()}))

        # streamed twin, concurrent with read load.  Two POSTs: the
        # first establishes the fragments' row capacity (that flush
        # folds — capacity growth changes device shapes), so the second
        # exercises the delta-overlay journal.
        half = n // 2
        from pilosa_tpu.ingest import wire
        post("/index/ings/field/fi/ingest",
             wire.encode_records(rows[:half], cols[:half],
                                 frame_records=10_000),
             "application/octet-stream")
        stop = threading.Event()
        conc: dict = {}

        def stream():
            body = wire.encode_records(rows[half:], cols[half:],
                                       frame_records=10_000)
            t0 = time.perf_counter()
            post("/index/ings/field/fi/ingest", body,
                 "application/octet-stream")
            conc["seconds"] = time.perf_counter() - t0
            conc["bytes"] = len(body)
            conc["records"] = n - half
            stop.set()

        t = threading.Thread(target=stream)
        t.start()
        qps_load, _ = _http_count_load(srv.port, "ings", "readf", 64,
                                       rng, 8, per_thread=24)
        t.join(timeout=300)
        assert stop.is_set(), "ingest stream never completed"

        def answers(field):
            out = []
            for r in (3, 17, 42):
                out.append(json.loads(post(
                    "/index/ings/query",
                    f"Count(Row({field}={r}))"))["results"])
            out.append(json.loads(post(
                "/index/ings/query", f"TopN({field}, n=5)"))["results"])
            return out

        live_journal = sum(fr.delta_bytes()
                           for *_x, fr in srv.holder.iter_fragments("ings"))
        assert live_journal > 0, \
            "second ingest stream never journaled a delta overlay"
        got_live = answers("fi")
        want = answers("fb")
        assert got_live == want, \
            "overlay-resident ingest answers diverged from bulk import"
        srv.committer.merge_all()  # fold the overlays
        assert answers("fi") == want, \
            "post-merge ingest answers diverged from bulk import"
        ing = srv.committer.snapshot()
        return {
            "records": n,
            "records_per_s": round(conc["records"] / conc["seconds"], 1),
            "ingest_mb_per_s": round(
                conc["bytes"] / conc["seconds"] / 1e6, 2),
            "read_qps_idle": round(qps_idle, 1),
            "read_qps_under_ingest": round(qps_load, 1),
            "read_qps_retention": round(qps_load / qps_idle, 3),
            "overlay_journal_bytes": live_journal,
            "flushes": ing["flushes"],
            "answers_identical": True,
        }
    finally:
        srv.close()


def bench_wholequery(holder, executor, meta, rng):
    """Whole-query legs (docs/whole-query.md): intersect8 (config-2
    corpus), bsi_sum (config-4), and filtered TopN (config-3) with the
    program path on (the serving default — ``executor``) vs a
    whole-query-off twin, plus the single-launch ledger check.  The
    on-path intersect8/bsi_sum qps are the numbers the r05 anchors
    judge; ratio is on/off on identical data and queries."""
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.utils import devobs

    off = Executor(holder, use_mesh=True, whole_query=False)
    out = {}
    try:
        # Batch/thread sizes are deliberately smaller than the config
        # 2/3/4 legs: a filtered row_counts launch materialises a
        # [B, rows, W] masked temp per stacked shard row, and deep
        # ticket fusion multiplies it — identically on BOTH paths (the
        # FUSED_ROWS_MAX cap predates this leg and does not scale by
        # fragment rows), so the on/off ratio is measured at sizes
        # every host can hold.
        legs = {
            "intersect8": ("startrace", lambda: " ".join(
                "Count(Intersect(" + ", ".join(
                    f"Row(stargazer={r})" for r in q) + "))"
                for q in _rand_rows(rng, meta["star_rows"], 1024)),
                16, 8),
            "bsi_sum": ("bsi64", lambda: " ".join(
                f"Sum(Row(v > {int(x)}), field=v)"
                for x in rng.integers(0, 1_000_000, size=32)), 8, 4),
            "topn": ("lang10m", lambda: " ".join(
                f"TopN(language, Row(stars={r}), n=50)"
                for r in rng.integers(0, 16, size=32)), 8, 4),
        }
        for name, (index, mk, nb, T) in legs.items():
            row = {}
            for label, ex in (("on", executor), ("off", off)):
                ex.execute(index, mk())  # warm compile + stacks

                def run(ex=ex, index=index, mk=mk, nb=nb, T=T):
                    return _run_batches(ex, index,
                                        [mk() for _ in range(nb)], T)

                d0 = _device_telemetry()
                (qps, _bat, _p50), spread = best_of(run)
                dev = _device_delta(d0)
                row[f"qps_{label}"] = round(qps, 1)
                row[f"spread_{label}"] = spread
                if label == "on":
                    row["device_on"] = dev
            row["ratio"] = round(row["qps_on"] / row["qps_off"], 3) \
                if row["qps_off"] else None
            out[name] = row
        # acceptance: a Count(Intersect)-class request is ONE ledger
        # entry of kind wholequery
        executor.execute(
            "startrace",
            "Count(Intersect(Row(stargazer=1), Row(stargazer=2)))")
        before = devobs.LEDGER.launches_total
        executor.execute(
            "startrace",
            "Count(Intersect(Row(stargazer=3), Row(stargazer=4)))")
        single = devobs.LEDGER.launches_total - before == 1
        entry = devobs.LEDGER.snapshot()["entries"][-1]
        out["single_launch"] = bool(single
                                    and entry["kind"] == "wholequery")
        out["wq_requests"] = executor.wq_requests
        out["wq_fallbacks"] = executor.wq_fallbacks
    finally:
        off.close()
    return out


def run_wholequery_smoke(rng) -> dict:
    """Whole-query leg of --smoke (docs/whole-query.md): a small corpus
    served with the program path on vs off — answers must be identical,
    a Count(Intersect)-class request must be exactly ONE launch on the
    ledger (kind wholequery), and on/off qps ride along (the
    r05-anchor floor is judged on real hardware by the full bench, not
    this CPU smoke)."""
    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage import FieldOptions, Holder
    from pilosa_tpu.utils import devobs

    h = Holder(None)
    idx = h.create_index("wq", track_existence=False)
    seg = idx.create_field("seg")
    metric = idx.create_field("metric")
    v = idx.create_field("v", FieldOptions(type="int", min=0,
                                           max=100_000))
    n = 200_000
    cols = rng.integers(0, 4 * SHARD_WIDTH, size=n)
    seg.import_bits(rng.integers(0, 8, size=n), cols)
    metric.import_bits(rng.integers(0, 8, size=n), cols)
    ucols = np.unique(cols)
    v.import_values(ucols, rng.integers(0, 100_000, size=ucols.size))

    on = Executor(h, use_mesh=True)
    off = Executor(h, use_mesh=True, whole_query=False)
    out = {}
    try:
        def batch(B=64):
            sets = _rand_rows(rng, 8, B)
            return " ".join(
                "Count(Intersect(" + ", ".join(
                    f"Row(seg={r})" for r in q[:4]) + "))"
                for q in sets)

        qs = [batch() for _ in range(8)]
        extra = [
            "Sum(Row(v > 5000), field=v)",
            "TopN(metric, Intersect(Row(seg=0), Row(seg=2)), n=5)",
            "Count(Intersect(Row(seg=1), Row(seg=3))) Sum(field=v) "
            "TopN(metric, n=3)",
        ]

        def norm(results):  # mixed kinds, unlike the TopN-only _smoke_norm
            return [[(p.id, p.count) for p in r] if isinstance(r, list)
                    else r for r in results]

        want = [norm(off.execute("wq", q)) for q in qs + extra]
        got = [norm(on.execute("wq", q)) for q in qs + extra]
        out["answers_identical"] = want == got
        assert out["answers_identical"], \
            "whole-query answers diverged from the legacy path"
        # single-launch-per-request, ledger-verified
        on.execute("wq", "Count(Intersect(Row(seg=2), Row(seg=5)))")
        before = devobs.LEDGER.launches_total
        on.execute("wq", "Count(Intersect(Row(seg=0), Row(seg=6)))")
        launches = devobs.LEDGER.launches_total - before
        entry = devobs.LEDGER.snapshot()["entries"][-1]
        out["single_launch"] = bool(launches == 1
                                    and entry["kind"] == "wholequery")
        assert out["single_launch"], \
            f"expected 1 wholequery launch, saw {launches}"
        out["wq_requests"] = on.wq_requests
        out["fallbacks"] = on.wq_fallbacks

        d0 = _device_telemetry()

        def timed(ex):
            t0 = time.perf_counter()
            served = 0
            for q in qs:
                served += len(ex.execute("wq", q))
            return served / (time.perf_counter() - t0)

        out["qps_off"] = round(timed(off), 1)
        out["qps_on"] = round(timed(on), 1)
        out["device"] = _device_delta(d0)
    finally:
        on.close()
        off.close()
    return out


def _smoke_norm(results):
    """TopN results -> comparable (id, count) lists."""
    return [[(p.id, p.count) for p in r] for r in results]


def run_overload_smoke() -> dict:
    """Overload-armor leg of --smoke (docs/robustness.md): drive the
    REAL server's admission and deadline paths so a regression in either
    shows in the bench trajectory.  A burst of 4x max-queries against a
    slot pool of 2 must yield only 200s/503s with both present, and a
    failpoint-delayed query under a 50 ms budget must 504 — asserted,
    then reported."""
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from pilosa_tpu.server.server import Config, Server
    from pilosa_tpu.utils.faults import FAULTS

    srv = Server(Config(data_dir=tempfile.mkdtemp(prefix="ptpu_smoke_"),
                        bind="localhost:0", anti_entropy_interval=0,
                        max_queries=2, queue_timeout=0.05))
    try:
        def post(path, body):
            req = urllib.request.Request(
                f"http://localhost:{srv.port}{path}", method="POST",
                data=body.encode())
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    resp.read()
                    return resp.status
            except urllib.error.HTTPError as e:
                e.read()
                return e.code

        srv.open()
        post("/index/sm", "{}")
        post("/index/sm/field/f", "{}")
        post("/index/sm/query", "Set(1, f=1) Set(1048579, f=1)")
        FAULTS.arm("mesh.slice", mode="delay", arg=0.15, match="sm")
        try:
            codes = []
            lock = threading.Lock()

            def one():
                c = post("/index/sm/query", "Count(Row(f=1))")
                with lock:
                    codes.append(c)

            threads = [threading.Thread(target=one) for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert set(codes) <= {200, 503}, f"burst statuses {set(codes)}"
            assert codes.count(200) >= 1 and codes.count(503) >= 1, codes
            t0 = time.perf_counter()
            code_504 = post("/index/sm/query?timeout=0.05",
                            "Count(Row(f=1))")
            deadline_s = time.perf_counter() - t0
            assert code_504 == 504, f"expected 504, got {code_504}"
        finally:
            FAULTS.disarm()
        return {"burst_200": codes.count(200),
                "burst_503": codes.count(503),
                "deadline_504_s": round(deadline_s, 3)}
    finally:
        srv.close()


def _clear_query_caches(ex):
    """Flush both cache layers (the /internal/cache/clear admin route's
    in-process analog) so a 'cold' measurement is genuinely cold."""
    from pilosa_tpu.cache.rank import iter_rank_caches

    ex.result_cache.clear()
    for _frag, cache in iter_rank_caches(ex.holder):
        cache.invalidate()


def run_cache_smoke(rng) -> dict:
    """Cache leg of --smoke (docs/caching.md): repeated unfiltered
    TopN/Count on unchanged data, cold (both cache layers flushed before
    every run) vs warm (result-cache hits).  Asserts the acceptance
    floor — warm >= 5x faster than cold — and reports the hit ratio."""
    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage import Holder

    h = Holder(None)
    idx = h.create_index("cachesmoke", track_existence=False)
    f = idx.create_field("f")
    n_bits = 200_000
    f.import_bits(rng.integers(0, 64, size=n_bits),
                  rng.integers(0, 4 * SHARD_WIDTH, size=n_bits))
    ex = Executor(h, use_mesh=True)
    ex.result_cache.limit_bytes = 64 << 20
    queries = ["TopN(f, n=10)", "Count(Row(f=7))",
               "Count(Intersect(Row(f=1), Row(f=2)))"]
    try:
        # compile warm-up with DISTINCT literals: the cold timings below
        # must measure execution + cache builds, not XLA compilation
        ex.execute("cachesmoke", "TopN(f, n=9) Count(Row(f=6)) "
                                 "Count(Intersect(Row(f=3), Row(f=4)))")

        def once():
            t0 = time.perf_counter()
            for q in queries:
                ex.execute("cachesmoke", q)
            return time.perf_counter() - t0

        colds = []
        for _ in range(3):
            _clear_query_caches(ex)
            colds.append(once())
        cold_s = float(np.median(colds))
        _clear_query_caches(ex)
        once()  # fill
        h0, m0 = ex.result_cache.hits, ex.result_cache.misses
        warms = [once() for _ in range(15)]
        warm_s = float(np.median(warms))
        hits = ex.result_cache.hits - h0
        misses = ex.result_cache.misses - m0
        speedup = cold_s / warm_s if warm_s > 0 else float("inf")
        assert hits == 15 * len(queries) and misses == 0, \
            f"warm repeats were not served from the cache " \
            f"({hits} hits, {misses} misses)"
        assert speedup >= 5, \
            f"warm repeats only {speedup:.1f}x faster than cold " \
            f"(acceptance floor is 5x)"
        return {
            "cold_ms": round(cold_s * 1e3, 2),
            "warm_ms": round(warm_s * 1e3, 3),
            "speedup": round(speedup, 1),
            "hit_ratio": round(hits / (hits + misses), 3),
            "resident_bytes": ex.result_cache.resident_bytes,
        }
    finally:
        ex.close()


def run_compressed_smoke(rng) -> dict:
    """Compressed-residency leg of --smoke (docs/memory-budget.md
    "Compressed residency"): the sparse corpus variant queried under a
    budget well below its dense working set must (a) hold the budget,
    (b) stage a compressed footprint smaller than the dense-resident
    one, and (c) return results identical to the dense-resident run —
    the three acceptance gates of the compressed path, end-to-end."""
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage import fragment as _frag
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET

    n_shards = 16
    h, oracle_words = build_config5(rng, n_shards=n_shards, sparse=True)
    ex = Executor(h, use_mesh=True)
    old_limit = DEFAULT_BUDGET.limit_bytes
    old_form = _frag.COMPRESSED_RESIDENT
    batches = [_cfg5_batch(rng, 8) for _ in range(4)]
    full_q = "TopN(metric, Intersect(Row(seg=1), Row(seg=3)), n=5)"
    try:
        # dense-resident reference (unlimited budget: compression is
        # off by design there — the heuristic requires a limit)
        _frag.COMPRESSED_RESIDENT = False
        DEFAULT_BUDGET.limit_bytes = None
        want = [_smoke_norm(ex.execute("ssb1b", b)) for b in batches]
        assert _smoke_norm(ex.execute("ssb1b", full_q))[0] == \
            oracle_topn5(oracle_words, range(n_shards), 1, 3), \
            "dense answer diverged from the oracle"
        dense_resident_mb = DEFAULT_BUDGET.stats()["residentBytes"] >> 20

        # compressed under a budget below the dense working set
        _frag.COMPRESSED_RESIDENT = True
        budget = 8 << 20
        DEFAULT_BUDGET.limit_bytes = budget
        DEFAULT_BUDGET.shrink_to_limit()
        DEFAULT_BUDGET.reset_peak()
        dev0 = _device_telemetry()
        t0 = time.perf_counter()
        got = [_smoke_norm(ex.execute("ssb1b", b)) for b in batches]
        compressed_s = time.perf_counter() - t0
        dev = _device_delta(dev0)
        assert got == want, \
            "compressed-resident results diverged from the dense run"
        stats = DEFAULT_BUDGET.stats()
        assert stats["peakBytes"] <= budget, \
            f"budget not held: peak {stats['peakBytes']} > {budget}"
        assert stats["compressedBytes"] > 0, \
            "no packed stream ever staged: the leg exercised nothing"
        compressed_mb = stats["compressedBytes"] / 2**20
        assert compressed_mb < dense_resident_mb, \
            (f"compressed footprint {compressed_mb:.1f}MB not below the "
             f"dense resident {dense_resident_mb}MB")
        # device-runtime telemetry (docs/observability.md "Device
        # runtime"): compressed launches must have decoded dense tiles
        # (the workspace high-watermark is the knob's feedback loop) and
        # the mixed-signature groups must have paid measurable bucket
        # padding — both exported at /metrics, asserted non-zero here
        assert dev["decode_mb"] > 0 and dev["decode_peak_mb"] > 0, \
            "compressed leg decoded nothing: workspace telemetry dead"
        assert dev["padding_waste_ratio"] > 0, \
            "compressed leg padded nothing: padding telemetry dead"
        return {
            "budget_held": True,
            "compressed_mb": round(compressed_mb, 2),
            "dense_resident_mb": dense_resident_mb,
            "effective_capacity_ratio": round(
                n_shards * 12 * 32768 * 4 / stats["compressedBytes"], 1),
            "compressed_s": round(compressed_s, 2),
            "device": dev,
        }
    finally:
        _frag.COMPRESSED_RESIDENT = old_form
        DEFAULT_BUDGET.limit_bytes = old_limit
        ex.close()


# Restart-leg worker (docs/warmup.md).  Inline rather than
# tests/crash_worker.py because the crash harness pins its Config — the
# restart leg needs the warm-start knobs and its own traffic shape.
# "seed" serves steady traffic, flushes the corpus, then parks until the
# parent kill -9s it mid-serving; "restart" boots on the same data dir,
# waits out the warming phase, and times the first query end-to-end.
_RESTART_WORKER = r'''
import json, os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
mode, data_dir = sys.argv[1], sys.argv[2]
from pilosa_tpu.server.server import Server, Config
s = Server(Config(data_dir=data_dir, bind="localhost:0",
                  timeseries_interval=0, metric_poll_interval=0,
                  anti_entropy_interval=0,
                  compile_cache_dir=os.path.join(data_dir,
                                                 ".compile-cache")))
s.open()
if mode == "seed":
    s.api.create_index("ri")
    s.api.create_field("ri", "f")
    s.api.query("ri", "".join(f"Set({c}, f={r})"
                              for r in range(4) for c in range(60)))
    for _ in range(3):
        s.api.query("ri", "Count(Row(f=1))")
        s.api.query("ri", "Row(f=2)")
        s.api.query("ri", "TopN(f, n=3)")
    s.warmup.recorder.flush(s.warmup.corpus)
    print("SEEDED", flush=True)
    time.sleep(600)  # the parent kill -9s us here: no clean close
else:
    t0 = time.monotonic()
    while s.warmup.warming() and time.monotonic() - t0 < 120:
        time.sleep(0.01)
    st = s.warmup.status()
    t1 = time.perf_counter()
    first = s.api.query("ri", "Count(Row(f=1))")
    first_ms = (time.perf_counter() - t1) * 1e3
    assert first == [60], first
    steady = []
    for _ in range(5):
        t2 = time.perf_counter()
        s.api.query("ri", "Count(Row(f=1))")
        steady.append((time.perf_counter() - t2) * 1e3)
    s.close()
    print(json.dumps({"warmup": st, "first_ms": round(first_ms, 2),
                      "steady_ms": round(min(steady), 2)}), flush=True)
'''


def run_restart_smoke(rng) -> dict:
    """Restart leg of --smoke (docs/warmup.md): seed a server with
    steady traffic, kill -9 it mid-serving, restart on the same data
    dir (warm: durable corpus + persistent compile cache survive), then
    restart again with both wiped (cold baseline).  The CPU smoke
    asserts the qualitative invariants — the warm restart replayed the
    corpus with ZERO retraces and its first query beats the cold
    restart's; the acceptance ratios (warm first-query p99 within ~2x
    steady state and >=5x better than cold) are judged on real
    hardware."""
    import os
    import shutil
    import subprocess
    import tempfile

    tmp = tempfile.mkdtemp(prefix="ptpu-restart-")
    # the workers keep their compile cache under tmp (an explicit
    # compile-cache-dir) so the cold restart can wipe it; a cache the
    # environment placed would outrank that
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def worker(mode):
        return subprocess.Popen(
            [sys.executable, "-c", _RESTART_WORKER, mode, tmp],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env)

    try:
        seed = worker("seed")
        line = seed.stdout.readline().strip()
        if line != "SEEDED":
            _, err = seed.communicate(timeout=30)
            raise AssertionError(f"seed worker failed: {err[-2000:]}")
        seed.kill()  # SIGKILL mid-serving: the crash-harness discipline
        seed.wait(timeout=30)
        assert os.path.exists(os.path.join(tmp, "signatures.log")), \
            "kill -9 lost the corpus: periodic flush never landed"

        warm_raw, warm_err = worker("restart").communicate(timeout=300)
        assert warm_raw.strip(), f"warm restart died: {warm_err[-2000:]}"
        warm = json.loads(warm_raw.strip().splitlines()[-1])
        wst = warm["warmup"]
        assert wst["replayed"] >= 1, \
            f"warm restart replayed nothing: {wst}"
        assert wst["errors"] == 0, f"warm replay errored: {wst}"
        assert wst["retracesDuringWarm"] == 0, \
            f"retraces during warm replay: {wst}"

        # cold baseline: no corpus, no compiled bytes
        os.unlink(os.path.join(tmp, "signatures.log"))
        shutil.rmtree(os.path.join(tmp, ".compile-cache"),
                      ignore_errors=True)
        cold_raw, cold_err = worker("restart").communicate(timeout=300)
        assert cold_raw.strip(), f"cold restart died: {cold_err[-2000:]}"
        cold = json.loads(cold_raw.strip().splitlines()[-1])
        assert cold["warmup"]["replayed"] == 0, cold["warmup"]
        assert warm["first_ms"] < cold["first_ms"], \
            (f"warm first query ({warm['first_ms']} ms) not faster than "
             f"cold ({cold['first_ms']} ms)")
        return {
            "replayed": wst["replayed"],
            "planned": wst["planned"],
            "retraces_during_warm": wst["retracesDuringWarm"],
            "saved_compile_s": wst["savedCompileS"],
            "warm_first_ms": warm["first_ms"],
            "cold_first_ms": cold["first_ms"],
            "steady_ms": warm["steady_ms"],
            "warm_vs_cold": round(cold["first_ms"]
                                  / max(warm["first_ms"], 1e-9), 1),
            "warm_vs_steady": round(warm["first_ms"]
                                    / max(warm["steady_ms"], 1e-9), 1),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_smoke():
    """--smoke: seconds-scale end-to-end exercise of the resident AND the
    budgeted/streaming query paths on tiny shard counts — wired as a
    slow-marked pytest (tests/test_bench_smoke.py) so the streaming
    pipeline is covered without bloating tier-1.  Asserts budgeted
    results are identical to the resident run and that eviction,
    streaming, and prefetch actually engaged; also drives the admission/
    deadline overload path (run_overload_smoke); prints one JSON line."""
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET

    rng = np.random.default_rng(SEED + 2)
    n_shards = 24
    h5, oracle_words = build_config5(rng, n_shards=n_shards)
    ex5 = Executor(h5, use_mesh=True)
    old_limit = DEFAULT_BUDGET.limit_bytes
    out = {"smoke": True, "shards": n_shards}
    t_start = time.perf_counter()
    try:
        subsets = [list(map(int, s))
                   for s in np.array_split(np.arange(n_shards), 4)]
        batches = [_cfg5_batch(rng, 8) for _ in range(6)]
        full_q = "TopN(metric, Intersect(Row(seg=0), Row(seg=2)), n=5)"

        # resident pass: no limit, everything stays staged
        DEFAULT_BUDGET.limit_bytes = None
        want = [ex5.execute("ssb1b", b, shards=subsets[i % 4])
                for i, b in enumerate(batches)]
        want_full = ex5.execute("ssb1b", full_q)
        assert _smoke_norm(want_full)[0] == \
            oracle_topn5(oracle_words, range(n_shards), 0, 2), \
            "resident answer diverged from the oracle"

        # budgeted pass: limit sized so two subset stacks cannot both
        # stay resident (per-subset ~12 MB stacked) and a full-shard
        # pass (~38 MB) must stream in slices with prefetch
        DEFAULT_BUDGET.limit_bytes = 20 << 20
        DEFAULT_BUDGET.shrink_to_limit()
        ev0 = DEFAULT_BUDGET.evictions
        pf0 = DEFAULT_BUDGET.prefetch_hits + DEFAULT_BUDGET.prefetch_misses
        t0 = time.perf_counter()
        got = [ex5.execute("ssb1b", b, shards=subsets[i % 4])
               for i, b in enumerate(batches)]
        got_full = ex5.execute("ssb1b", full_q)
        budgeted_s = time.perf_counter() - t0
        for w, g in zip(want, got):
            assert _smoke_norm(w) == _smoke_norm(g), \
                "budgeted subset results diverged from the resident run"
        assert _smoke_norm(want_full) == _smoke_norm(got_full), \
            "streamed full-pass result diverged from the resident run"
        stats = DEFAULT_BUDGET.stats()
        assert DEFAULT_BUDGET.evictions > ev0, \
            "budget never evicted under the smoke limit"
        assert stats["prefetchHits"] + stats["prefetchMisses"] > pf0, \
            "streaming prefetch never engaged on the over-budget pass"
        out.update({
            "budgeted_s": round(budgeted_s, 2),
            "evictions": DEFAULT_BUDGET.evictions - ev0,
            "prefetch_hits": stats["prefetchHits"],
            "prefetch_misses": stats["prefetchMisses"],
            "upload_mb": stats["uploadBytes"] >> 20,
            "pinned_bytes": stats["pinnedBytes"],
        })
    finally:
        DEFAULT_BUDGET.limit_bytes = old_limit
        ex5.close()
    out["wholequery"] = run_wholequery_smoke(
        np.random.default_rng(SEED + 9))
    out["routing"] = run_routing_smoke(np.random.default_rng(SEED + 10))
    out["chaos"] = run_chaos_smoke(np.random.default_rng(SEED + 11))
    out["slo"] = run_slo_smoke(np.random.default_rng(SEED + 16))
    out["wire"] = run_wire_smoke(np.random.default_rng(SEED + 12))
    out["tenant"] = run_tenant_smoke(np.random.default_rng(SEED + 13))
    out["compressed"] = run_compressed_smoke(np.random.default_rng(SEED + 6))
    out["ssb"] = run_ssb_smoke(np.random.default_rng(SEED + 15))
    out["ingest"] = run_ingest_smoke(np.random.default_rng(SEED + 8))
    out["cache"] = run_cache_smoke(np.random.default_rng(SEED + 3))
    out["overload"] = run_overload_smoke()
    out["http_batch"] = run_http_batch_smoke(np.random.default_rng(SEED + 4))
    out["observability"] = run_observability_smoke(
        np.random.default_rng(SEED + 5),
        baseline_qps=out["http_batch"]["qps_on"])
    out["restart"] = run_restart_smoke(np.random.default_rng(SEED + 14))
    out["total_s"] = round(time.perf_counter() - t_start, 2)
    out.update(_device_row())
    print(json.dumps(out))


def main():
    from pilosa_tpu.executor import Executor

    holder, meta = build_indexes()
    executor = Executor(holder, use_mesh=True)
    rng = np.random.default_rng(SEED + 1)

    d0 = _device_telemetry()
    q1, l1, p1, b1, s1 = bench_config1(executor, meta, rng)
    dev1, d0 = _device_delta(d0), _device_telemetry()
    q2, l2, p2, b2, s2 = bench_config2(executor, meta, rng)
    dev2, d0 = _device_delta(d0), _device_telemetry()
    q3, l3, p3, b3, s3 = bench_config3(executor, meta, rng)
    dev3, d0 = _device_delta(d0), _device_telemetry()
    q4, l4, p4, b4, gb_s, gb_grid_s, s4 = bench_config4(executor, meta,
                                                        rng)
    dev4 = _device_delta(d0)

    (c1,), _ = best_of(lambda: (cpu_config1(holder, meta, rng),))
    (c2,), _ = best_of(lambda: (cpu_config2(holder, meta, rng),))
    (c3,), _ = best_of(lambda: (cpu_config3(holder, meta, rng),))
    (c4,), _ = best_of(lambda: (cpu_config4(holder, meta, rng),))

    # sanity: engine answers match the numpy oracle on one query per config
    frag = _np_frag(holder, "startrace", "stargazer")[0]
    got = executor.execute("startrace", "Count(Row(stargazer=14))")[0]
    assert got == int(np.bitwise_count(frag[14]).sum()), "config1 mismatch"

    from pilosa_tpu.executor import Executor as _Ex
    h5, oracle_words = build_config5(rng)
    ex5 = _Ex(h5, use_mesh=True)
    try:
        # answer-equality: engine TopN == word-wise oracle on a full pass
        got5 = ex5.execute(
            "ssb1b", "TopN(metric, Intersect(Row(seg=0), Row(seg=2)), n=5)")
        want5 = oracle_topn5(oracle_words, range(N_SHARDS5), 0, 2)
        assert [(p.id, p.count) for p in got5[0]] == want5, \
            f"config5 mismatch: {got5[0]} != {want5}"
        # resident variant: all 4 subset stacks fit (954 shards x 12 rows
        # x 128KB  stacked ~1.6GB; 6GB leaves staging headroom)
        d5 = _device_telemetry()
        cfg5r = bench_config5(ex5, oracle_words, rng, 6144, resident=True)
        cfg5r["device"], d5 = _device_delta(d5), _device_telemetry()
        cfg5 = bench_config5(ex5, oracle_words, rng, 768, resident=False)
        cfg5["device"] = _device_delta(d5)
    finally:
        ex5.close()
    failed: list[str] = []

    def leg(name, fn, *args):
        """Run one leg after the headline configs.  A leg that raises is
        named under "failed_legs" and makes main() exit non-zero — an
        absent row is never how a failure shows."""
        try:
            return fn(*args)
        except Exception:
            import traceback
            print(f"{name} failed:", file=sys.stderr)
            traceback.print_exc()
            failed.append(name)
            return None

    # compressed-residency leg (docs/memory-budget.md): the over-budget
    # cliff on the sparse corpus, compressed vs dense vs resident anchor
    def compressed_leg():
        d5c = _device_telemetry()
        out = bench_config5_compressed(np.random.default_rng(SEED + 7))
        out["device"] = _device_delta(d5c)
        return out

    cfg5c = leg("7_topn_1B_cols_sparse_compressed", compressed_leg)
    # SSB star-schema config: resident vs compressed-over-budget,
    # per-leg kernel backend
    ssb_leg = leg("14_ssb_star_schema", bench_ssb,
                  np.random.default_rng(SEED + 15))
    cfg5d = leg("5d_intersect_topn_4node_cluster",
                bench_config5_distributed, rng)
    # elastic-serving config (docs/cluster.md): skewed-hot-index corpus,
    # loaded routing vs primary-pinned on a replica_n=2 cluster
    routing_leg = leg("10_elastic_routing", bench_routing,
                      np.random.default_rng(SEED + 10))
    # tail-tolerance config (docs/robustness.md "Tail-tolerant
    # fan-out"): ChaosProxy straggler p99 with hedging on vs off
    chaos_leg = leg("11_tail_tolerance_chaos", bench_chaos,
                    np.random.default_rng(SEED + 11))
    # SLO/alerting config (docs/observability.md "SLOs & alerting"):
    # straggler fire -> bundle -> resolve + evaluation-overhead pair
    slo_leg = leg("20_slo_alerting", bench_slo,
                  np.random.default_rng(SEED + 16))
    # internal-wire config (docs/cluster.md "Internal query wire"):
    # binary PTPUQRY1 vs JSON envelope on the same recorded fan-out
    # corpus, answers asserted byte-identical
    wire_leg = leg("12_internal_wire", bench_wire,
                   np.random.default_rng(SEED + 12))
    # tenant-isolation config (docs/robustness.md "Tenant isolation"):
    # polite-tenant p99 under a hostile flood, fair admission on vs off
    tenant_leg = leg("13_tenant_isolation", bench_tenant,
                     np.random.default_rng(SEED + 13))
    # concurrent-HTTP dynamic-batching config (docs/batching.md): the
    # served single-query path, dispatch-batch on vs off
    http_batch = leg("6_http_dynamic_batching",
                     bench_http_dynamic_batching, holder, executor, meta,
                     rng)
    # streaming-ingest config (docs/ingest.md): sustained write rate and
    # the read-qps retention under concurrent ingest
    ingest_leg = leg("8_streaming_ingest", bench_ingest, holder, executor,
                     meta, np.random.default_rng(SEED + 8))
    # whole-query config (docs/whole-query.md): program path on vs off
    # on the config-2/3/4 corpora + the single-launch ledger check
    wq_leg = leg("9_whole_query", bench_wholequery, holder, executor, meta,
                 np.random.default_rng(SEED + 9))

    # HTTP variant (engine behind the real server)
    def http_leg():
        import tempfile
        from pilosa_tpu.server import Config, Server
        srv = Server(Config(data_dir=tempfile.mkdtemp(prefix="ptpu_bench_"),
                            bind="localhost:0", anti_entropy_interval=0))
        srv.holder.indexes = holder.indexes  # serve the bench data
        srv.api.holder = holder
        srv.api.executor = executor
        srv.open()
        try:
            return bench_http(srv.port, rng, meta["star_rows"])
        finally:
            srv.httpd.shutdown()

    http_qps = leg("2_http_path", http_leg)

    configs = {
        "1_count_row_1shard": {
            "qps": round(q1, 1), "batch_ms": round(l1 * 1e3, 1),
            "batch_p50_ms": round(p1 * 1e3, 1),
            "spread": s1, "vs_cpu": round(q1 / c1, 2),
            "cpu_qps": round(c1, 1),
            **_bandwidth(q1, b1),
            "device": dev1},
        "2_intersect8_1M_cols": {
            "qps": round(q2, 1), "batch_ms": round(l2 * 1e3, 1),
            "batch_p50_ms": round(p2 * 1e3, 1),
            "spread": s2, "vs_cpu": round(q2 / c2, 2),
            "cpu_qps": round(c2, 1),
            **_bandwidth(q2, b2),
            "device": dev2},
        "3_topn_filtered_10M_cols": {
            "qps": round(q3, 1), "batch_ms": round(l3 * 1e3, 1),
            "batch_p50_ms": round(p3 * 1e3, 1),
            "spread": s3, "vs_cpu": round(q3 / c3, 2),
            "cpu_qps": round(c3, 2),
            **_bandwidth(q3, b3, frac=True),
            "device": dev3},
        "4_bsi_sum_gt_64shards": {
            "qps": round(q4, 1), "batch_ms": round(l4 * 1e3, 1),
            "batch_p50_ms": round(p4 * 1e3, 1),
            "spread": s4, "vs_cpu": round(q4 / c4, 2),
            "cpu_qps": round(c4, 2),
            **_bandwidth(q4, b4, frac=True),
            "groupby_s": round(gb_s, 3),
            "groupby_128x128_s": round(gb_grid_s, 3),
            "device": dev4},
        "5_topn_1B_cols_resident": cfg5r,
        "5_topn_1B_cols_budgeted": cfg5,
    }
    if cfg5c:
        configs["7_topn_1B_cols_sparse_compressed"] = cfg5c
    if cfg5d:
        configs["5d_intersect_topn_4node_cluster"] = cfg5d
    if http_qps:
        configs["2_http_path"] = {"qps": round(http_qps, 1)}
    if http_batch:
        configs["6_http_dynamic_batching"] = http_batch
    if ingest_leg:
        configs["8_streaming_ingest"] = ingest_leg
    if wq_leg:
        configs["9_whole_query"] = wq_leg
    if routing_leg:
        configs["10_elastic_routing"] = routing_leg
    if chaos_leg:
        configs["11_tail_tolerance_chaos"] = chaos_leg
    if slo_leg:
        configs["20_slo_alerting"] = slo_leg
    if wire_leg:
        configs["12_internal_wire"] = wire_leg
    if tenant_leg:
        configs["13_tenant_isolation"] = tenant_leg
    if ssb_leg:
        configs["14_ssb_star_schema"] = ssb_leg

    dev = _device_row()
    for row in configs.values():
        row.update(dev)
    print(json.dumps({
        "metric": "engine_intersect8_count_qps_1M_cols",
        "value": round(q2, 1),
        "unit": "queries/sec",
        "vs_baseline": round(q2 / c2, 2),
        **dev,
        "configs": configs,
        "failed_legs": failed,
    }))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        run_smoke()
    else:
        main()
