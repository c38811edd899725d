"""The Pallas container kernel (ops/kernels.py): goldens of the fused
count per container form against the ``unpack_packed`` host oracle, by
the kernel (interpreted) and by its XLA form, under one filter, several
and none, alone and under the call site's ``vmap``; backend selection
(the ``container-kernels`` knob, its kill switch, and the static
per-field rule); the device_sig kernel-backend axis (a flip must
rebuild stacks, not retrace — the PR 7 retrace class); and the 3-LEG
DIFFERENTIAL: a mixed-forms corpus executed dense-resident,
compressed-jnp, and compressed-pallas-interpret must return
byte-identical results with zero retrace alarms.  Answers come from the
Pallas INTERPRETER on the CPU tier-1 platform — the same kernel logic a
TPU compiles — and ``test_kernels_lower_for_tpu`` lowers the kernel for
the TPU with ``interpret=False``, so a kernel that stops lowering fails
here and not on the next chip run."""

import numpy as np
import pytest

from conftest import over_budget_limit

from pilosa_tpu.core import CONTAINER_WORDS, SHARD_WIDTH, SHARD_WORDS
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops import containers, kernels
from pilosa_tpu.ops.containers import (
    ARRAY_WORDS_MAX, RUN_MAX, pack_words, pad_packed, pow2_bucket,
    stream_bucket, unpack_packed, upload_decode,
)
from pilosa_tpu.storage import FieldOptions, Holder
from pilosa_tpu.storage.fragment import Fragment
from pilosa_tpu.storage.membudget import DEFAULT_BUDGET, DeviceBudget
from pilosa_tpu.utils import devobs

from test_differential import _norm, gen_query


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def force_backend():
    """Set the container-kernels knob for one test, restoring after —
    the per-test analog of the server config apply."""
    old = kernels.CONTAINER_KERNELS

    def _set(mode):
        kernels.CONTAINER_KERNELS = mode

    yield _set
    kernels.CONTAINER_KERNELS = old


BACKENDS = ("pallas", "jnp")


def _buckets(p):
    return dict(a_bucket=stream_bucket(p.a_len),
                r_bucket=pow2_bucket(p.r_max))


def _kernel_golden(idx, val, rows, backend, filters: int = 2):
    """The fused count of a packed stream — by the Pallas kernel
    (interpret mode on CPU) or its XLA form — under no filter and under
    ``filters`` random ones, vs the numpy host oracle; returns the
    Packed stream for form assertions."""
    import jax.numpy as jnp
    p = pack_words(idx, val)
    arrs = [jnp.asarray(a) for a in pad_packed(p)]
    dense = unpack_packed(p, rows)
    kw = dict(rows=rows, backend=backend, **_buckets(p))
    got = np.asarray(kernels.fused_row_counts(*arrs, None, **kw))
    np.testing.assert_array_equal(got, _popcounts(dense)[None])
    filts = np.random.default_rng(idx.size).integers(
        0, 1 << 32, (filters, SHARD_WORDS), dtype=np.uint64) \
        .astype(np.uint32)
    got = np.asarray(kernels.fused_row_counts(
        *arrs, jnp.asarray(filts.reshape(filters, 256, 128)), **kw))
    np.testing.assert_array_equal(
        got, np.stack([_popcounts(dense & f[None]) for f in filts]))
    return p


def _popcounts(dense):
    return np.unpackbits(
        np.ascontiguousarray(dense).view(np.uint8), axis=1).sum(
            axis=1).astype(np.int32)


# -- per-container-form kernel goldens vs the host oracle -------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_kernel_array_boundary(rng, backend):
    """Array containers right at the array<->bitmap threshold on both
    sides count exactly."""
    for n in (1, ARRAY_WORDS_MAX - 1, ARRAY_WORDS_MAX):
        slots = np.sort(rng.choice(CONTAINER_WORDS, n, replace=False))
        idx = (3 * CONTAINER_WORDS + slots).astype(np.int64)
        val = rng.integers(1, 1 << 32, n, dtype=np.uint64) \
            .astype(np.uint32)
        p = _kernel_golden(idx, val, 2, backend)
        assert p.type_histogram()["array"] >= 1


@pytest.mark.parametrize("backend", BACKENDS)
def test_kernel_bitmap(rng, backend):
    """A over-threshold container packs as bitmap and is counted as a
    block of the payload."""
    n = ARRAY_WORDS_MAX + 1
    slots = np.sort(rng.choice(CONTAINER_WORDS, n, replace=False))
    idx = slots.astype(np.int64)
    val = rng.integers(1, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    p = _kernel_golden(idx, val, 1, backend)
    assert p.type_histogram()["bitmap"] == 1


@pytest.mark.parametrize("backend", BACKENDS)
def test_kernel_run_boundary(backend):
    """Run containers at RUN_MAX runs (and the single full-container
    run) count via their range masks exactly."""
    # RUN_MAX disjoint 3-word runs of all-ones words (long enough that
    # 2 payload words per run beats the array form's 2 per word)
    idx = (np.arange(RUN_MAX)[:, None] * 4
           + np.arange(3)[None, :]).reshape(-1).astype(np.int64)
    val = np.full(idx.size, 0xFFFFFFFF, dtype=np.uint32)
    p = _kernel_golden(idx, val, 1, backend)
    assert p.type_histogram()["run"] == 1
    # one full container of ones -> a single run
    idx2 = np.arange(CONTAINER_WORDS, dtype=np.int64) + CONTAINER_WORDS
    val2 = np.full(CONTAINER_WORDS, 0xFFFFFFFF, dtype=np.uint32)
    p2 = _kernel_golden(idx2, val2, 1, backend)
    assert p2.type_histogram()["run"] == 1
    assert int(p2.counts[p2.types == containers.TYPE_RUN][0]) == 1


@pytest.mark.parametrize("backend", BACKENDS)
def test_kernel_empty_and_mixed(rng, backend):
    """Empty stream (zeros) and a mixed-form fragment spanning several
    rows, under as many filters as one fused count takes."""
    _kernel_golden(np.zeros(0, np.int64), np.zeros(0, np.uint32), 2,
                   backend)
    rows = 4
    parts_i, parts_v = [], []
    # sparse scatter (arrays) across all rows
    i0 = np.sort(rng.choice(rows * SHARD_WORDS, 400, replace=False))
    parts_i.append(i0.astype(np.int64))
    parts_v.append(rng.integers(1, 1 << 32, 400, dtype=np.uint64)
                   .astype(np.uint32))
    # a dense container (bitmap) in row 1
    i1 = SHARD_WORDS + 7 * CONTAINER_WORDS + np.arange(CONTAINER_WORDS)
    parts_i.append(i1.astype(np.int64))
    parts_v.append(rng.integers(1, 1 << 32, CONTAINER_WORDS,
                                dtype=np.uint64).astype(np.uint32))
    # a run container (all ones) in row 3
    i2 = 3 * SHARD_WORDS + 2 * CONTAINER_WORDS + np.arange(CONTAINER_WORDS)
    parts_i.append(i2.astype(np.int64))
    parts_v.append(np.full(CONTAINER_WORDS, 0xFFFFFFFF, dtype=np.uint32))
    flat = np.concatenate(parts_i)
    vals = np.concatenate(parts_v)
    order = np.argsort(flat)
    flat, vals = flat[order], vals[order]
    keep = np.concatenate([[True], np.diff(flat) != 0])
    p = _kernel_golden(flat[keep], vals[keep], rows, backend,
                       filters=kernels.FUSED_PARAMS_MAX)
    h = p.type_histogram()
    assert h["array"] and h["bitmap"] and h["run"]


def test_fused_row_counts_under_vmap(rng):
    """The call site maps the count over the stacked shard axis: the
    kernel's grid gains an axis, its accumulator starts anew at every
    shard's first block, and fragments of different stream lengths
    share one padded shape."""
    import jax
    import jax.numpy as jnp
    rows, S = 3, 4
    packs = []
    for s in range(S):
        n = 300 + 2500 * s          # one block of entries up to several
        flat = np.sort(rng.choice(rows * SHARD_WORDS, n, replace=False)) \
            .astype(np.int64)
        packs.append(pack_words(flat, rng.integers(
            1, 1 << 32, n, dtype=np.uint64).astype(np.uint32)))
    assert len({p.a_len for p in packs}) > 1
    ab = stream_bucket(max(p.a_len for p in packs))
    cb = max(pow2_bucket(p.keys.size) for p in packs)
    stacked = []
    for p in packs:
        keys, types, counts, offsets, payload, a_idx, a_val = pad_packed(p)

        def wide(a, n, fill):
            out = np.full(a.shape[:-1] + (n,), fill, dtype=a.dtype)
            out[..., : a.shape[-1]] = a
            return out

        stacked.append((wide(keys, cb, -1), wide(types, cb, -1),
                        wide(counts, cb, 0), wide(offsets, cb, 0), payload,
                        wide(a_idx, ab, containers.ARRAY_PAD),
                        wide(a_val, ab, 0)))
    arrs = [jnp.asarray(np.stack(col)) for col in zip(*stacked)]
    filts = rng.integers(0, 1 << 32, (S, 2, 256, 128), dtype=np.uint64) \
        .astype(np.uint32)
    want = np.stack([
        np.stack([_popcounts(unpack_packed(p, rows) & f.reshape(1, -1))
                  for f in fs]) for p, fs in zip(packs, filts)])
    for backend in BACKENDS:
        fn = jax.jit(jax.vmap(lambda *a, _b=backend: kernels.fused_row_counts(
            *a, rows=rows, a_bucket=ab, r_bucket=0, backend=_b)))
        np.testing.assert_array_equal(
            np.asarray(fn(*arrs, jnp.asarray(filts))), want)


def test_budget_rule_selects_statically(force_backend, monkeypatch):
    """``auto`` on a TPU selects the kernel per field, from the
    signature alone: a field whose accumulator would not fit VMEM is a
    jnp signature from the start (never a lowering error answered by
    jnp), and a forced ``pallas`` is never replaced."""
    monkeypatch.setattr(kernels, "_platform", lambda: "tpu")
    force_backend("auto")
    assert kernels.fits(128) and kernels.backend_for(128) == "pallas"
    most = kernels.VMEM_BUDGET_BYTES // (
        kernels.FUSED_PARAMS_MAX * kernels.TILE_LANES * 4)
    assert kernels.backend_for(most) == "pallas"
    assert not kernels.fits(most + 1)
    assert kernels.backend_for(most + 1) == "jnp"
    force_backend("pallas")
    assert kernels.backend_for(most + 1) == "pallas"
    force_backend("jnp")
    assert kernels.backend_for(128) == "jnp"


def test_kill_switch_lowers_no_kernel(force_backend, monkeypatch, rng):
    """``container-kernels=jnp`` keeps Pallas off every path, on a TPU
    too: the count a jnp signature compiles lowers no custom call, and
    neither does either decoder under any knob."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setattr(kernels, "_platform", lambda: "tpu")
    rows = 4
    flat = np.sort(rng.choice(rows * SHARD_WORDS, 5000, replace=False)) \
        .astype(np.int64)
    p = pack_words(flat, np.ones(5000, dtype=np.uint32))
    arrs = [jnp.asarray(a) for a in pad_packed(p)]
    filt = jnp.ones((1, 256, 128), dtype=jnp.uint32)

    def lowered(fn, *a):
        return jax.jit(fn).trace(*a).lower(
            lowering_platforms=("tpu",)).as_text()

    for knob, kernel in (("jnp", False), ("auto", True), ("pallas", True)):
        force_backend(knob)
        backend = kernels.backend_for(rows)
        text = lowered(lambda *a: kernels.fused_row_counts(
            *a, rows=rows, backend=backend, **_buckets(p)), *arrs, filt)
        assert ("tpu_custom_call" in text) == kernel, knob
        for dec in (lambda *a: containers.decode_block(
                        *a, rows=rows, **_buckets(p)),
                    lambda *a: containers.decode_row(
                        *a, jnp.int32(1), rows=rows, **_buckets(p))):
            assert "tpu_custom_call" not in lowered(dec, *arrs), knob


# -- backend resolution and the device_sig backend axis ---------------------

def test_resolve_backends(force_backend, monkeypatch):
    """Knob semantics: jnp is the kill switch, pallas forces the
    kernels, auto picks by platform (jnp on the CPU tier-1 box).  On a
    TPU nothing interprets and nothing forced turns into jnp."""
    import jax
    assert jax.default_backend() == "cpu"
    force_backend("jnp")
    assert kernels.resolve() == "jnp"
    force_backend("pallas")
    assert kernels.resolve() == "pallas"
    force_backend("auto")
    assert kernels.resolve() == "jnp"
    assert kernels.interpret_mode()
    monkeypatch.setattr(kernels, "_platform", lambda: "tpu")
    assert kernels.resolve() == "pallas"
    assert not kernels.interpret_mode()
    force_backend("pallas")
    assert kernels.resolve() == "pallas"


def _smoke_buckets():
    """(rows, C, P, A, R) decode buckets of chip_smoke.py's sparse
    corpus — the signatures its compressed leg hands the kernels on the
    chip — from real fragments of a few generated shards."""
    import chip_smoke
    budget = DeviceBudget(limit_bytes=1 << 16)  # no dense form fits it
    out = set()
    for shard in range(3):
        words = chip_smoke.shard_words(7, shard, sparse=True)
        for lo, hi in ((0, chip_smoke.SEG_ROWS),
                       (chip_smoke.SEG_ROWS, chip_smoke.ROWS)):
            f = Fragment(None, "i", "f", "standard", shard, budget=budget)
            for r in range(lo, hi):
                f.set_row(r - lo, words[r])
            sig = f.device_sig()
            assert sig[0] == "z", sig
            out.add(sig[1:6])
    return sorted(out)


def _aot_tpu():
    """scripts/aot_tpu.py as a module: the one definition of "every
    kernel over a decode bucket" that the lowering test here and the
    deviceless compile there share."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "aot_tpu.py")
    spec = importlib.util.spec_from_file_location("aot_tpu", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernels_lower_for_tpu(monkeypatch):
    """The kernel lowers for the TPU with ``interpret=False`` — the
    Pallas->Mosaic lowering, run from the CPU by cross-platform
    lowering — over the smoke corpus's buckets, the benchmark's largest
    field and a bitmap-only bucket, without a filter and under 1 and 4,
    under the call site's vmap over the stacked shard axis.  (What only
    the TPU compiler itself can refuse — VMEM limits, layouts — is what
    ``fits`` bounds, test_kernels_compile_for_v5e compiles and
    chip_smoke.py runs.)"""
    import jax
    monkeypatch.setattr(kernels, "_platform", lambda: "tpu")
    aot = _aot_tpu()
    generated = _smoke_buckets()
    assert set(generated) <= set(aot.SMOKE_BUCKETS), generated
    buckets = list(aot.SMOKE_BUCKETS + aot.BENCH_BUCKETS) + [
        (4, 64, 1 << 17, 0, 0)]
    lowered = 0
    for bucket in buckets:
        rows, _, _, A, _ = bucket
        assert kernels.backend_for(rows) == "pallas", bucket
        for name, (fn, avals) in aot.kernel_cases(bucket).items():
            text = jax.jit(fn).trace(*avals).lower(
                lowering_platforms=("tpu",)).as_text()
            # the one kernel counts the array entries: a bucket
            # without arrays has none
            assert ("tpu_custom_call" in text) == (A > 0), (bucket, name)
            lowered += 1
    assert lowered == 3 * len(buckets)


def test_kernels_compile_for_v5e():
    """The other half of test_kernels_lower_for_tpu: the TPU compiler
    itself, Mosaic included, against a deviceless v5e topology
    (scripts/aot_tpu.py) — VMEM, SMEM and layouts, which lowering
    alone cannot see.  In a subprocess: libtpu initialises there, not
    in the test process.  Skipped where libtpu offers no topology."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "aot_tpu.py")],
        capture_output=True, text=True, timeout=600, cwd=root)
    if out.returncode == 3:
        pytest.skip(f"no deviceless TPU topology here: {out.stdout[-300:]}")
    report = json.loads(out.stdout.strip().splitlines()[-1])
    bad = [k for k in report["kernels"] if not k["compiled"]]
    assert out.returncode == 0 and not bad, bad or out.stderr[-2000:]
    assert all(k["auto_selects"] == "pallas" for k in report["kernels"])
    aot = _aot_tpu()
    assert len(report["kernels"]) == 3 * len(
        aot.SMOKE_BUCKETS + aot.BENCH_BUCKETS)


def test_device_sig_backend_axis(force_backend):
    """Satellite regression (the PR 7 retrace class): flipping
    container-kernels changes a compressed fragment's device_sig — new
    signatures mean new plan keys and stack tokens, so the flip rebuilds
    instead of replaying a jnp-compiled executable with pallas-shaped
    expectations.  Dense signatures carry no backend axis."""
    budget = DeviceBudget(limit_bytes=1 << 19)  # under the 1 MiB dense form
    f = Fragment(None, "i", "f", "standard", 0, budget=budget)
    f.bulk_import(np.arange(8), np.arange(8) * 1000)
    assert f.device_form() == "compressed"
    force_backend("jnp")
    sig_jnp = f.device_sig()
    assert sig_jnp[0] == "z" and sig_jnp[6] == "jnp"
    force_backend("pallas")
    sig_pl = f.device_sig()
    assert sig_pl[6] == "pallas" and sig_pl[:6] == sig_jnp[:6]
    # the sig cache is keyed by (gen, backend): flipping back must
    # return the jnp sig again, not the cached pallas one
    force_backend("jnp")
    assert f.device_sig() == sig_jnp
    assert kernels.sig_backend(sig_pl) == "pallas"
    # pre-backend-axis 6-tuples read as jnp (the decode they compiled)
    assert kernels.sig_backend(sig_jnp[:6]) == "jnp"


def test_upload_decode_is_xla(force_backend):
    """The standalone compressed-upload decode is XLA's under every
    knob: it decodes every row, which no kernel does, so it launches
    none and the ledger's kernel count stands."""
    force_backend("pallas")
    rng = np.random.default_rng(3)
    flat = np.sort(rng.choice(2 * SHARD_WORDS, 120, replace=False)) \
        .astype(np.int64)
    vals = rng.integers(1, 1 << 32, 120, dtype=np.uint64) \
        .astype(np.uint32)
    p = pack_words(flat, vals)
    before = devobs.LEDGER.kernel_launches_total
    got = np.asarray(upload_decode(p, 2))     # [2, 256, 128]
    np.testing.assert_array_equal(got.reshape(2, SHARD_WORDS),
                                  unpack_packed(p, 2))
    assert devobs.LEDGER.kernel_launches_total == before


# -- 3-leg differential on the mixed-forms corpus ---------------------------

@pytest.fixture(scope="module")
def corpus():
    """4-shard index mixing sparse scatter (arrays), boundary-dense
    containers (bitmaps), run-heavy clustered ranges, BSI values, and an
    emptied fragment — the PR 7 mixed corpus at a size the interpreted
    kernels execute quickly."""
    rng = np.random.default_rng(99)
    h = Holder(None)
    idx = h.create_index("k")
    a = idx.create_field("a")
    b = idx.create_field("b")
    v = idx.create_field("v", FieldOptions(type="int", min=-500, max=500))
    n = 12_000
    cols = rng.integers(0, 4 * SHARD_WIDTH, size=n)
    a.import_bits(rng.integers(0, 10, size=n), cols)
    b.import_bits(rng.integers(0, 6, size=n), cols)
    # run-heavy clustered ranges in every shard
    run_cols = np.concatenate([
        np.arange(s * SHARD_WIDTH + 1000, s * SHARD_WIDTH + 30_000)
        for s in range(4)])
    a.import_bits(np.full(run_cols.size, 11), run_cols)
    vcols = np.unique(cols[: n // 2])
    v.import_values(vcols, rng.integers(-500, 500, size=vcols.size))
    idx.add_existence(np.unique(np.concatenate([cols, run_cols])))
    # emptied fragment: set then clear (empty packed stream)
    ecols = np.arange(2 * SHARD_WIDTH + 50, 2 * SHARD_WIDTH + 80)
    b.import_bits(np.full(30, 5), ecols)
    b.import_bits(np.full(30, 5), ecols, clear=True)
    return h


def _run_corpus(ex, queries):
    return [_norm(r) for q in queries for r in ex.execute("k", q)]


def test_three_leg_differential(corpus, force_backend):
    """dense-resident / compressed-jnp / compressed-pallas-interpret
    are byte-identical on the mixed corpus; the pallas leg records
    kernel launches in the ledger; and the whole run — including the
    backend flip — raises ZERO retrace alarms (flips mint new
    signatures, they don't retrace old ones)."""
    qrng = np.random.default_rng(1234)
    queries = [gen_query(qrng) for _ in range(3)]
    queries += ["TopN(a, n=3)", "Count(Row(a=11))", "Row(b=5)",
                "Count(Intersect(Row(a=11), Row(b=2)))",
                "Sum(Row(a=1), field=v)"]
    # the whole-query program decodes every compressed input whole; the
    # per-stage launcher is where rows are taken one by one and counted
    # in the packed stream (mesh_exec._build), kernel included
    ex = Executor(corpus, use_mesh=True)
    stage = Executor(corpus, use_mesh=True, whole_query=False)
    old = DEFAULT_BUDGET.limit_bytes
    retraces0 = devobs.COMPILES.totals()["retraces"]
    try:
        # leg 1 — dense-resident reference (no budget limit, no
        # compression, backend knob irrelevant)
        DEFAULT_BUDGET.limit_bytes = None
        force_backend("jnp")
        want = _run_corpus(ex, queries)
        assert _run_corpus(stage, queries) == want

        # leg 2 — compressed residency, XLA's count (the kill switch);
        # the kill-switch leg must not launch any container kernel
        DEFAULT_BUDGET.limit_bytes = over_budget_limit(corpus)
        DEFAULT_BUDGET.shrink_to_limit()
        kj = devobs.LEDGER.kernel_launches_total
        assert _run_corpus(ex, queries) == want
        assert _run_corpus(stage, queries) == want
        st = DEFAULT_BUDGET.stats()
        assert st["compressedBytes"] > 0, \
            "corpus never compressed: the differential exercised " \
            "only the dense path"
        assert devobs.LEDGER.kernel_launches_total == kj, \
            "jnp kill-switch leg launched container kernels"

        # leg 3 — compressed residency, the Pallas kernel (interpreted
        # on CPU): same bytes, plus kernel launches in the ledger
        force_backend("pallas")
        assert _run_corpus(ex, queries) == want
        k0 = devobs.LEDGER.kernel_launches_total
        assert _run_corpus(stage, queries) == want
        assert devobs.LEDGER.kernel_launches_total > k0, \
            "pallas leg never launched a container kernel"

        # flip back: the kill switch restores the jnp path in place
        force_backend("jnp")
        assert _run_corpus(ex, queries) == want
        assert _run_corpus(stage, queries) == want
    finally:
        DEFAULT_BUDGET.limit_bytes = old
        ex.close()
        stage.close()
    assert devobs.COMPILES.totals()["retraces"] == retraces0, \
        "backend flip retraced an existing signature instead of " \
        "minting new ones"
