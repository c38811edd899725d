"""Worker process for test_multihost.py: one of N jax.distributed CPU
processes forming ONE multi-host engine (multihost mode 2 —
parallel/multihost.py; the role the reference's gossip+HTTP data plane
plays across nodes, http/client.go:268 QueryNode).

Every process runs this same script in SPMD lockstep: it imports only its
own shard slice host-side (import_process_slice), joins the global mesh,
and executes an identical query sequence whose collectives (psum,
all_gather) cross process boundaries over the distributed runtime.
Answers are asserted against a full-data numpy oracle; the parent test
checks every process printed MULTIHOST OK.

Usage: multihost_worker.py <coordinator_port> <process_id> <n_processes>
"""

import os
import sys


def main():
    port, pid, nproc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    from pilosa_tpu.parallel.multihost import (
        global_mesh, import_process_slice, init_distributed,
    )
    init_distributed(f"localhost:{port}", nproc, pid)

    import jax
    import numpy as np

    assert jax.process_count() == nproc, jax.process_count()
    assert len(jax.devices()) == 4 * nproc

    # Environment probe (ROADMAP item 3): some jaxlib builds accept
    # distributed init on CPU but implement NO cross-process collectives
    # — the first psum dies with "Multiprocess computations aren't
    # implemented on the CPU backend".  Probe with a trivial collective
    # BEFORE the heavy import machinery so unsupported environments fail
    # fast with a distinctive marker the parent test turns into a skip
    # (every process runs the same probe in lockstep, so none is left
    # hanging in a half-started collective).
    try:
        jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")(
            np.ones((jax.local_device_count(),), dtype=np.float32))
    except jax.errors.JAXTypeError:
        raise
    except Exception as e:  # XlaRuntimeError lives in jaxlib; match wide
        msg = str(e).replace("\n", " ")
        if "implemented on the CPU backend" in msg or \
                "Multiprocess" in msg:
            print(f"MULTIHOST UNSUPPORTED proc={pid}: {msg[:300]}",
                  flush=True)
            sys.exit(42)
        raise

    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.ops import bsi
    from pilosa_tpu.storage import FieldOptions, Holder

    n_shards = 8
    rng = np.random.default_rng(21)  # same stream on every process
    h = Holder(None)
    idx = h.create_index("mh", track_existence=False)
    f = idx.create_field("f")
    n = 20000
    cols = rng.integers(0, n_shards * SHARD_WIDTH, size=n)
    rows = rng.integers(0, 6, size=n)
    lo, hi = import_process_slice(f, rows, cols, n_shards, max_row_id=5)
    assert (hi - lo) == n_shards // nproc

    # BSI field: same per-slice import; remote shards get shape-matched
    # empty fragments at the GLOBAL bit depth (part of the executable's
    # shape signature, so it must agree on every process)
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    vcols = np.unique(cols)[::3]
    vvals = rng.integers(1, 1000, size=vcols.size)
    sel = (vcols >= lo * SHARD_WIDTH) & (vcols < hi * SHARD_WIDTH)
    v.import_values(vcols[sel], vvals[sel])
    depth = int(vvals.max()).bit_length()
    bview = v._create_view_if_not_exists(v.bsi_view_name())
    for s in range(n_shards):
        fr = bview.create_fragment_if_not_exists(s)
        if fr.n_rows <= bsi.OFFSET_ROW + depth - 1:
            fr.set_row(bsi.OFFSET_ROW + depth - 1, None)

    ex = Executor(h, mesh=global_mesh())

    # oracle over the FULL data (each process imported only a slice)
    by_row = {r: set(cols[rows == r].tolist()) for r in range(6)}
    val_of = dict(zip(vcols.tolist(), vvals.tolist()))

    # 1: Count (psum across processes)
    [cnt] = ex.execute("mh", "Count(Row(f=3))")
    assert cnt == len(by_row[3]), (cnt, len(by_row[3]))
    # 2: Intersect+Count
    [cnt] = ex.execute("mh", "Count(Intersect(Row(f=1), Row(f=2)))")
    assert cnt == len(by_row[1] & by_row[2])
    # 3: Row segments (all_gather across processes)
    [row] = ex.execute("mh", "Row(f=1)")
    assert set(row.columns()) == by_row[1]
    # 4: TopN
    [topn] = ex.execute("mh", "TopN(f, n=3)")
    exact = sorted(((len(v_), -r) for r, v_ in by_row.items()),
                   reverse=True)
    assert [p.count for p in topn] == [c for c, _ in exact[:3]]
    # 5: Sum with filter
    [s_] = ex.execute("mh", "Sum(Row(f=2), field=v)")
    want = sum(val_of.get(c, 0) for c in by_row[2])
    assert s_.val == want, (s_.val, want)
    # 6: Min/Max (per-shard extrema gathered across processes)
    [mn] = ex.execute("mh", "Min(field=v)")
    [mx] = ex.execute("mh", "Max(field=v)")
    assert mn.val == int(vvals.min()) and mx.val == int(vvals.max())
    # 7: GroupBy + Rows
    [rws] = ex.execute("mh", "Rows(f)")
    assert rws.rows == sorted(by_row)
    [gb] = ex.execute("mh", "GroupBy(Rows(f), Rows(f))")
    gb_map = {(g.group[0].row_id, g.group[1].row_id): g.count
              for g in gb}
    for a in range(6):
        for b in range(6):
            want = len(by_row[a] & by_row[b])
            assert gb_map.get((a, b), 0) == want, (a, b)

    print(f"MULTIHOST OK proc={pid}", flush=True)


if __name__ == "__main__":
    main()
