"""``taxi-1b-mesh4``: upstream's taxi example at its documented 954
shards on one node whose mesh is the four chips of a host
(benchmark/configs/taxi-1b-mesh4.json, PERF.md PR 35), held here on the
CPU at 10 shards — a count no mesh divides — to what the deployment
forces:

* the ``topn-year-pcount`` mix through the served path on the default
  mesh of the eight virtual devices, and the same TopN program through
  a ``MeshExecutor`` over four of them: every template alone, in bodies
  of 2 and 4 calls and as four concurrent requests answers exactly what
  the plain reference's cube says;
* what the device budget counts is what each device holds: the block
  over four devices is a quarter a device, and ``/debug/vars`` says so;
* the bucket rule at four devices, the configuration file, the two new
  layer metrics' files and their readers on hand-made traces.
"""

import json
import os

import jax
import pytest

from test_ssb_sf30 import BENCH, REPO, Deployment, _bench, _four_at_once

CONFIG, MIX = "taxi-1b-mesh4", "topn-year-pcount"
CELL = "taxi-1b-mesh4.topn-year-pcount"
SEED = 3500000035
SHARDS = 10


class MeshDeployment(Deployment):
    """The configuration at ``SHARDS`` shards behind one ``Server`` on
    the default mesh (``test_ssb_sf30.Deployment``), and one
    ``Executor`` over the same holder whose mesh is four of the
    devices."""

    def __init__(self):
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.parallel.mesh_exec import default_mesh
        super().__init__(SHARDS, CONFIG, MIX, SEED)
        self.mesh4 = Executor(self.srv.holder,
                              mesh=default_mesh(jax.devices()[:4]),
                              whole_query_fallback="error")

    def served(self, pick: list) -> list:
        return self.client.query(self.index, self.body(pick))

    def on_four(self, pick: list) -> list:
        return [[p.to_dict() for p in r]
                for r in self.mesh4.execute(self.index, self.body(pick))]

    def close(self):
        self.mesh4.close()
        super().close()


@pytest.fixture(scope="module")
def dep():
    d = MeshDeployment()
    yield d
    d.close()


PATHS = ["served", "on_four"]


@pytest.mark.parametrize("size", [1, 2, 4])
@pytest.mark.parametrize("path", PATHS)
def test_topn_exact(dep, path, size):
    """Every distinct request drawn from the mix's one template, in
    bodies of ``size`` calls."""
    (ids,) = dep.by_template.values()
    assert len(ids) >= 24
    for skip in range(0, len(ids), size):
        pick = dep.pick(0, size, skip)
        assert getattr(dep, path)(pick) == dep.expected(pick), pick
    if path == "served":
        assert dep.vars()["wholeQuery"]["fallbacks"] == 0
    else:
        assert dep.mesh4.wq_fallbacks == 0 and dep.mesh4.wq_requests > 0


@pytest.mark.parametrize("path", PATHS)
def test_four_concurrent_requests_exact(dep, path):
    """Four single-call requests from four threads, released together:
    the batcher may fuse them; each gets its own exact answer."""
    for got, want in _four_at_once(dep, 0, getattr(dep, path)):
        assert got == want


# -- what the budget counts is what a device holds ----------------------------


def test_block_over_four_devices_is_a_quarter_a_device(dep):
    """The stacked blocks of the four-device executor: 10 shards go to a
    bucket of 16, four a device; each block registers its bytes over all
    devices and the fullest device's quarter of them."""
    me = dep.mesh4.mesh_exec
    dep.on_four(dep.pick(0, 1))
    assert me.n_devices == 4 and me._bucket(SHARDS) == 16
    with me._sc_lock:
        blocks = list(me._blocks.values())
    assert {b.bkey[1][0] for b in blocks} == {
        "dist_miles", "pickup_year", "passenger_count"}
    for b in blocks:
        rows = b.arrays.shape[1]
        assert b.nbytes == 16 * rows * (128 << 10) == b.arrays.nbytes
        assert b.devices == 4 and 4 * b.device_bytes == b.nbytes
        shards = b.arrays.addressable_shards
        assert {s.data.nbytes for s in shards} == {b.device_bytes}
        entry = me._budget._entries[b.skey]
        assert (entry[0], entry[5], entry[6]) == (b.nbytes, b.device_bytes,
                                                  4)
    assert me.stack_block_bytes() == sum(b.nbytes for b in blocks)


def test_debug_vars_say_what_a_device_holds(dep):
    dep.served(dep.pick(0, 1))
    v = dep.vars()
    budget = v["deviceBudget"]
    assert budget["devices"] == 8
    assert 0 < budget["residentBytesMaxDevice"] < budget["residentBytes"]
    mem = v["device"]["memory"]
    assert [m["id"] for m in mem] == [d.id for d in jax.local_devices()]
    assert len(mem) == v["device"]["deviceCount"] == 8
    # the CPU's allocator reports nothing: nulls, not zeros
    assert all(set(m) == {"id", "bytesInUse", "peakBytesInUse",
                          "bytesLimit"} for m in mem)
    assert all(m["bytesInUse"] is None for m in mem)


# -- the bucket, the files, the readers ----------------------------------------


def test_bucket_of_the_documented_scale():
    from pilosa_tpu.parallel.mesh_exec import MeshExecutor

    class Mesh:
        n_devices = 4
        _bucket = MeshExecutor._bucket

    assert Mesh()._bucket(954) == 960
    assert MeshExecutor.stacked_per_device(Mesh(), 954) == 240
    assert Mesh()._bucket(SHARDS) == 16


def test_the_configuration_is_the_source_at_its_scale():
    datagen = _bench()[0]
    cut, full = (datagen.load_json("configs", c)
                 for c in ("taxi-256", CONFIG))
    assert full["shards"] == 954 == full["published"]["shards"]
    assert full["published"] == {"shards": 954, "rides": 1000000000,
                                 "fields": 20}
    assert full["reduced"] == ["fields"] == list(full["reduced_why"])
    moved = {k for k in cut if cut[k] != full[k]}
    assert moved == {"name", "source", "deployment", "shards", "reduced",
                     "reduced_why"}
    assert set(cut) == set(full)


def test_the_cell_is_declared():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = bench["workloads"][4]
    assert cell == {**cell, "name": CELL, "config": CONFIG, "traffic": MIX,
                    "chips": 4}
    assert len(cell["why"]) <= 200
    # the one cell whose mechanism exists only across chips
    assert [w["name"] for w in bench["workloads"] if w["chips"] != 1] == \
        [CELL]
    config = bench["configs"][3]
    assert config == {**config, "name": CONFIG, "reduced": ["fields"],
                      "file": f"benchmark/configs/{CONFIG}.json"}
    datagen = _bench()[0]
    assert config["source"] == datagen.load_json("configs", CONFIG)["source"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("kernels_roofline_per_chip")
    assert at == 20 and names[at + 1] == "collective_share_top10"
    # of the metrics PR 35 found or added (the first 23), all but two
    listed = {n for n, m in by_name.items() if CELL in m["workloads"]}
    assert set(names[:23]) - listed == {"kernels_roofline",
                                        "temp_split_share"}
    for name in ("kernels_roofline_per_chip", "collective_share_top10"):
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "qps"


def _reader(metric: str):
    """(the reader's module, the metric's spec) by the names
    ``run.layer_metrics`` finds them by."""
    import importlib
    _bench()
    with open(os.path.join(BENCH, "layer_metrics", f"{metric}.json")) as f:
        spec = json.load(f)
    assert os.path.exists(
        os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    return importlib.import_module(f"readers.{spec['reader']}"), spec


def _trace(devices: int, ops: list, busy_s: float = 2.0) -> dict:
    return {"devices": devices, "busy_s": busy_s, "window_s": 3.0,
            "idle_share": 1.0 - busy_s / 3.0, "device_ops": ops,
            "idle_gaps": [], "op_count": 10}


def _ctx(trace, least_bytes=8.19e11) -> dict:
    return {"spans": {"trace": {"least_bytes": least_bytes, "n": 100}},
            "trace": trace, "device": {"kind": "TPU v5 lite"}}


TEN_OPS = [["%convert_reduce_fusion s32[64]", 1.9],
           ["%and_bitcast_fusion u32[240,256,128]", 0.06]] + \
    [[f"%fusion.{i} u32[240,256,128]", 0.001] for i in range(8)]


def test_roofline_per_chip_is_a_quarter_on_four():
    per_chip, spec = _reader("kernels_roofline_per_chip")
    one_chip, _ = _reader("kernels_roofline")
    assert spec["reader"] == "roofline_share_per_chip"
    on_one = per_chip.read(spec, _ctx(_trace(1, TEN_OPS)))
    # 8.19e11 bytes in 2.0 busy seconds of 819 GB/s: half the roofline
    assert on_one == pytest.approx(50.0)
    assert on_one == one_chip.read({}, _ctx(_trace(1, TEN_OPS)))
    on_four = per_chip.read(spec, _ctx(_trace(4, TEN_OPS)))
    assert on_four == pytest.approx(on_one / 4)
    assert one_chip.read({}, _ctx(_trace(4, TEN_OPS))) == on_one
    assert per_chip.read(spec, _ctx(None)) is None
    assert per_chip.read(spec, _ctx(_trace(4, TEN_OPS), 0)) is None
    with pytest.raises(KeyError):
        per_chip.read(spec, {**_ctx(_trace(4, TEN_OPS)),
                             "device": {"kind": "cpu"}})


def test_collective_share_of_the_ten_largest():
    reader, spec = _reader("collective_share_top10")
    assert spec["reader"] == "collective_share"
    assert reader.read(spec, _ctx(_trace(4, TEN_OPS))) == 0.0
    assert reader.read(spec, _ctx(None)) is None
    ops = TEN_OPS[:6] + [["%all-reduce.1 s32[64]", 0.05],
                         ["all-gather-start.2 u32[4,64]", 0.03],
                         ["%reduce-scatter s32[16]", 0.01],
                         ["%collective-permute-done.3 u32[8]", 0.01]]
    assert reader.read(spec, _ctx(_trace(4, ops))) == \
        pytest.approx(100 * 0.10 / 2.0)
    # what the v5e's trace calls the psum of a shard_map body (my chip
    # runs, PR 35): XLA names the all-reduce after the jax primitive
    ops = TEN_OPS[:3] + [["%psum_invariant.7 s32[1,64]{1,0:T(1,128)}",
                          0.004]] + TEN_OPS[3:9]
    assert reader.read(spec, _ctx(_trace(4, ops))) == \
        pytest.approx(100 * 0.004 / 2.0)
    # a fusion that only mentions a reduce is no collective
    assert reader.read(spec, _ctx(_trace(
        4, [["%convert_reduce_fusion s32[64]", 1.0],
            ["%all_reduce_like_fusion s32[64]", 1.0]]))) == 0.0
