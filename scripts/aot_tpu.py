#!/usr/bin/env python3
"""Compile for a TPU v5e without one.

libtpu can describe a deviceless topology, and jax compiles against it
with the whole TPU compiler, Mosaic included — so what otherwise only a
chip run shows (VMEM and SMEM limits, layout errors in a Pallas kernel)
shows here in seconds.  The executable cannot run; answers still come
from interpret mode on the CPU and from chip_smoke.py on the chip.

``compile_for_v5e(fn, *avals)`` is the helper.  Run as a script it
compiles the container kernel ``auto`` selects (ops/kernels.py), under
the call site's vmap, over the buckets given as rows:C:P:A:R
arguments (default: chip_smoke.py's sparse corpus and the benchmark's
largest compressed field), and prints one JSON
line.  Exit 0 all compiled · 1 a compile failed · 3 no topology here.
"""

from __future__ import annotations

import json
import os
import sys
import time

if __name__ == "__main__":
    # a compile-only process: never reach for a chip, find the package
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# (rows, C, P, A, R) decode buckets of chip_smoke.py's sparse corpus
# (seed 7), 32 class streams a fragment
SMOKE_BUCKETS = ((4, 64, 0, 256, 0), (4, 64, 0, 512, 0),
                 (8, 128, 0, 512, 0), (8, 128, 128, 512, 64))
# ...and the two fields the benchmark's taxi-1b-chip1 counts in place,
# as its generator packs them (total_amount_dollars, 53 stacked shards a
# launch; dist_miles, up to 96), a group's streams 9 216 and 2 304 lanes
BENCH_BUCKETS = ((128, 2048, 589824, 9216, 0), (64, 1024, 294912, 2304, 0))


def v5e_topology():
    """libtpu's description of a v5e host without its chips."""
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def kernel_cases(bucket, stacked: int = 2) -> dict:
    """{name: (fn, avals)} — the container kernel of ops/kernels.py over
    one (rows, C, P, A, R) bucket, under the call site's vmap over
    ``stacked`` fragments, without a filter and under 1, 2 and 4: each
    step width (``kernels.step_lanes``)."""
    from pilosa_tpu.core import WORD_TILE
    from pilosa_tpu.ops import kernels
    from pilosa_tpu.ops.containers import ARRAY_STREAMS
    rows, C, P, A, R = bucket
    kw = dict(rows=rows, a_bucket=A, r_bucket=R, backend="pallas")

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct((stacked,) + shape, dtype)

    packed = [aval((C,), jnp.int32)] * 4 + [
        aval((P,), jnp.uint32), aval((ARRAY_STREAMS, A), jnp.int32),
        aval((ARRAY_STREAMS, A), jnp.uint32)]
    cases = {"fused_row_counts": (
        jax.vmap(lambda *a: kernels.fused_row_counts(*a, None, **kw)),
        packed)}
    for b in (1, 2, 4):
        cases[f"fused_row_counts+filter{b}"] = (
            jax.vmap(lambda *a: kernels.fused_row_counts(*a, **kw)),
            packed + [aval((b,) + WORD_TILE, jnp.uint32)])
    return cases


def compile_for_v5e(fn, *avals, topology=None):
    """Lower ``fn`` for the TPU and compile it for one deviceless v5e
    chip; raises what the compiler raises."""
    topo = topology or v5e_topology()
    s = jax.sharding.SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
            for a in avals]
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


def main(argv) -> int:
    from pilosa_tpu.ops import kernels
    buckets = [tuple(int(x) for x in a.split(":")) for a in argv] \
        or list(SMOKE_BUCKETS + BENCH_BUCKETS)
    try:
        topo = v5e_topology()
    except Exception as e:  # whatever libtpu raises without a topology
        print(json.dumps({"topology": None, "error": repr(e)[:500]}))
        return 3
    kernels._platform = lambda: "tpu"   # compile, never interpret
    rows_out, failed = [], 0
    for bucket in buckets:
        rows, _, P, A, R = bucket
        for name, (fn, avals) in kernel_cases(bucket).items():
            t0 = time.perf_counter()
            row = {"kernel": name, "bucket": list(bucket),
                   "auto_selects": kernels.backend_for(rows)}
            try:
                compile_for_v5e(fn, *avals, topology=topo)
                row["compiled"] = True
            except Exception as e:  # the compiler's own error, reported
                row["compiled"] = False
                row["error"] = str(e)[:800]
                failed += 1
            row["host_s"] = round(time.perf_counter() - t0, 2)
            rows_out.append(row)
    print(json.dumps({"topology": topo.devices[0].device_kind,
                      "failed": failed, "kernels": rows_out}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
