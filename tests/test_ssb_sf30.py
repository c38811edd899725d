"""``ssb-q1-sf30``: the Star Schema Benchmark's flight 1 at the size that
fills one chip (benchmark/configs/ssb-q1-sf30.json, PERF.md PR 31), held
here on the CPU at a few shards to what the deployment forces:

* every template of the ``q1-flight`` mix, alone, in bodies of 2 and 4
  calls and as four concurrent single-call requests, answers exactly
  what the plain reference's cube says — at the default batch-temp bound
  and with the bound forced under the compiler's figure for one lone
  request, where a launch walks its shards in blocks, a pack is cut and
  a multi-call body goes back to the chunked path;
* what a launch holds against the bound is the compiler's own figure
  for the program it runs, read with the one compile the launch pays;
* the shard-axis bucket rule; the two new layer metrics' data files.
"""

import json
import os
import sys
import tempfile
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CONFIG, MIX = "ssb-q1-sf30", "q1-flight"
SEED = 3100000031


def _bench():
    """The benchmark's own modules (it is no package: they import each
    other by bare name from ``benchmark/``)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import datagen
    import loader
    import oracle
    import serving
    import traffic
    return datagen, loader, oracle, serving, traffic


class Deployment:
    """One ``Server`` with a configuration loaded at ``shards`` shards
    as ``benchmark/run.py`` loads it, its cube and its requests."""

    def __init__(self, shards: int, config: str = CONFIG, mix: str = MIX,
                 seed: int = SEED):
        datagen, loader, oracle, serving, traffic = _bench()
        self.shards = shards
        self.cfg = datagen.load_json("configs", config)
        self.mix = datagen.load_json("traffic", mix)
        self.tmp = tempfile.TemporaryDirectory(prefix=f"ptpu-{config}-")
        self.srv, self.client = serving.open_server(
            os.path.join(self.tmp.name, "data"), serving.device_info())
        loader.create_schema(self.client, self.cfg)
        self.cube = oracle.Cube(self.cfg, self.mix)
        loader.load(self.srv.holder, self.cfg, seed, shards, self.cube)
        self.requests = traffic.Requests(self.cfg, self.mix, seed,
                                         per_client=48)
        self.by_template: dict = {}
        for i, t in enumerate(self.requests.template):
            self.by_template.setdefault(t, []).append(i)
        self.index = self.cfg["index"]["name"]

    def pick(self, template: int, size: int, skip: int = 0) -> list:
        ids = self.by_template[template]
        return [ids[(skip + k) % len(ids)] for k in range(size)]

    def body(self, pick: list) -> str:
        return " ".join(self.requests.pql[i] for i in pick)

    def expected(self, pick: list) -> list:
        return [self.requests.expected(i, self.cube) for i in pick]

    def vars(self) -> dict:
        return self.client.debug_vars()

    def close(self):
        self.srv.close()
        self.tmp.cleanup()


_DEPLOYMENTS: dict = {}


@pytest.fixture(scope="module")
def deployment():
    """``deployment(shards)``: one per shard count for the module."""
    def get(shards: int) -> Deployment:
        if shards not in _DEPLOYMENTS:
            _DEPLOYMENTS[shards] = Deployment(shards)
        return _DEPLOYMENTS[shards]
    yield get
    for d in _DEPLOYMENTS.values():
        d.close()
    _DEPLOYMENTS.clear()


def _q12_programs(dep) -> dict:
    """The compiled Q1.2 programs of ``dep``'s executor: (padded batch
    rows, shard blocks or None) -> the instrumented executable."""
    mesh = dep.srv.api.executor.mesh_exec
    return {(k[3][0][0], k[5][2] if len(k) > 5 else None): fn
            for k, fn in list(mesh._cache.items())
            if k[0] == "wholequery" and "d_yearmonthnum" in k[1]}


@pytest.fixture
def forced_bound(deployment, monkeypatch):
    """The bound forced between the compiler's figure for one lone Q1.2
    request over ONE stacked shard and its figure over the two a device
    holds at 12 shards."""
    from pilosa_tpu.parallel import nodes
    dep = deployment(12)
    assert dep.srv.api.executor.mesh_exec.stacked_per_device(12) == 2
    lone = dep.body(dep.pick(1, 1))
    dep.client.query(dep.index, lone)
    (whole,) = _q12_programs(dep)[1, None]._temps.values()
    monkeypatch.setattr(nodes, "BATCH_TEMP_BYTES", whole - 1)
    dep.client.query(dep.index, lone)
    (one,) = _q12_programs(dep)[1, (1,)]._temps.values()
    assert 0 < one < whole
    monkeypatch.setattr(nodes, "BATCH_TEMP_BYTES", (one + whole) // 2)
    return dep


TEMPLATES = [(0, "q1.1"), (1, "q1.2"), (2, "q1.3")]


@pytest.mark.parametrize("size", [1, 2, 4])
@pytest.mark.parametrize("template", [t for t, _ in TEMPLATES],
                         ids=[n for _, n in TEMPLATES])
@pytest.mark.parametrize("shards", [3, 5])
def test_flight_exact_at_the_default_bound(deployment, shards, template,
                                           size):
    dep = deployment(shards)
    before = dep.vars()
    pick = dep.pick(template, size)
    assert dep.client.query(dep.index, dep.body(pick)) == dep.expected(pick)
    after = dep.vars()
    assert after["wholeQuery"]["fallbacks"] == \
        before["wholeQuery"]["fallbacks"]
    assert after["batchTemp"]["splits"] == before["batchTemp"]["splits"]
    assert after["batchTemp"]["boundBytes"] == 4 << 30


def _four_at_once(dep, template: int, ask=None) -> list:
    """Four single-call requests of one template from four threads,
    released together: the batcher may fuse them.  ``ask(pick)`` sends
    one (the served path where None)."""
    picks = [dep.pick(template, 1, skip=k) for k in range(4)]
    got: list = [None] * 4
    gate = threading.Barrier(4)
    if ask is None:
        def ask(pick):
            return dep.client.query(dep.index, dep.body(pick))

    def one(k):
        gate.wait()
        got[k] = ask(picks[k])

    threads = [threading.Thread(target=one, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [(g, dep.expected(p)) for g, p in zip(got, picks)]


@pytest.mark.parametrize("template", [t for t, _ in TEMPLATES],
                         ids=[n for _, n in TEMPLATES])
@pytest.mark.parametrize("shards", [3, 5])
def test_four_concurrent_requests_exact(deployment, shards, template):
    dep = deployment(shards)
    before = dep.vars()["wholeQuery"]["fallbacks"]
    for got, want in _four_at_once(dep, template):
        assert got == want
    assert dep.vars()["wholeQuery"]["fallbacks"] == before


@pytest.mark.parametrize("size", [1, 2, 4])
@pytest.mark.parametrize("template", [t for t, _ in TEMPLATES],
                         ids=[n for _, n in TEMPLATES])
def test_flight_exact_under_a_forced_bound(forced_bound, template, size):
    """A lone request walks its two shards in blocks of one and stays a
    whole-query program; a body of 2 or 4 does not fit over one shard
    (by the compiler's figure for it) and goes back to the chunked
    path.  Exact either way."""
    dep = forced_bound
    before = dep.vars()
    pick = dep.pick(template, size)
    assert dep.client.query(dep.index, dep.body(pick)) == dep.expected(pick)
    after = dep.vars()
    assert after["batchTemp"]["boundBytes"] < 4 << 30
    assert after["batchTemp"]["splits"] > before["batchTemp"]["splits"]
    moved = after["wholeQuery"]["fallbacks"] - \
        before["wholeQuery"]["fallbacks"]
    assert moved == (0 if size == 1 else 1)


@pytest.mark.parametrize("template", [t for t, _ in TEMPLATES],
                         ids=[n for _, n in TEMPLATES])
def test_four_concurrent_requests_under_a_forced_bound(forced_bound,
                                                       template):
    """The packer cuts a pack whose fused rows would not fit over one
    shard; every request still gets its own exact answer on the
    whole-query path."""
    dep = forced_bound
    before = dep.vars()
    for got, want in _four_at_once(dep, template):
        assert got == want
    after = dep.vars()
    assert after["batchTemp"]["splits"] > before["batchTemp"]["splits"]
    assert after["wholeQuery"]["fallbacks"] == \
        before["wholeQuery"]["fallbacks"]


# -- what a launch holds against the bound is the compiler's figure ---------


@pytest.mark.parametrize("size", [1, 2, 4])
@pytest.mark.parametrize("shards", [5, 12])
def test_launch_reads_the_compilers_figure(deployment, monkeypatch, shards,
                                           size):
    """A Q1.2 body's first launch reads
    ``memory_analysis().temp_size_in_bytes`` of the executable it then
    runs: one program built (the registry's count and jax's own agree),
    a positive figure that is the compiler's for that shape, and
    nothing built or read again on the repeat."""
    from pilosa_tpu.parallel import wholequery as wq
    dep = deployment(shards)
    mesh = dep.srv.api.executor.mesh_exec
    reads = []
    read = wq._InstrumentedWhole.temp_bytes

    def spy(self, local, mats, flat):
        out = read(self, local, mats, flat)
        reads.append((self, local, mats, flat) + out)
        return out

    monkeypatch.setattr(wq._InstrumentedWhole, "temp_bytes", spy)
    with mesh._lock:
        for key in [k for k in mesh._cache if k[0] == "wholequery"]:
            del mesh._cache[key]
    pick = dep.pick(1, size)
    before = dep.vars()["device"]["compiles"]
    assert dep.client.query(dep.index, dep.body(pick)) == dep.expected(pick)
    after = dep.vars()["device"]["compiles"]
    (fn, local, mats, flat, temp, traced), = reads
    assert traced and mats[0].shape[0] == size
    assert local == (mesh.stacked_per_device(shards),)
    assert after["compiles"] == before["compiles"] + 1
    xla = fn.fn.lower(mats, *flat).compile().memory_analysis()
    assert temp == xla.temp_size_in_bytes > 0
    built = dep.vars()["device"]["compiles"]["backendCompiles"]
    assert dep.client.query(dep.index, dep.body(pick)) == dep.expected(pick)
    assert reads[-1][4:] == (temp, False)
    assert dep.vars()["device"]["compiles"]["backendCompiles"] == built


def test_bound_is_what_the_device_has_left(monkeypatch):
    """``bytes_limit`` less the fullest device's resident bytes less
    the margin, under the ``batch-temp-mb`` ceiling; the ceiling alone
    where the backend reports no limit (here)."""
    from pilosa_tpu.executor import executor as exmod
    from pilosa_tpu.parallel import nodes
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
    assert nodes.device_bytes_limit() is None
    assert nodes.batch_temp_bound() == nodes.BATCH_TEMP_BYTES
    resident = DEFAULT_BUDGET.resident_bytes_max_device

    def limit(nbytes):
        monkeypatch.setattr(nodes, "device_bytes_limit", lambda: nbytes)

    limit(resident + (16 << 30))
    assert nodes.batch_temp_bound() == nodes.BATCH_TEMP_BYTES
    limit(resident + (3 << 30))
    assert nodes.batch_temp_bound() == 2 << 30
    limit(resident)
    assert nodes.batch_temp_bound() == 0
    # nothing fits: a chunk is still one row, the launch still runs
    assert exmod.batch_chunk_size(2, 4) == 1
    # a filtered Sum over a 32-row field at 16 stacked shards, the bound
    # in rows a shard: no 8-row floor, a power of two under the bound
    rows = nodes.node_temp_rows("bsi_sum", object(), 3, 32)
    assert rows == 32 == nodes.node_temp_rows("row_counts", object(), 3, 32)
    assert nodes.node_temp_rows("bsi_sum", None, 0, 32) == 0
    assert nodes.node_temp_rows("count", object(), 3) == 3
    for left, chunk in ((31, 1), (32, 1), (63, 1), (64, 2), (255, 4),
                        (256, 8)):
        limit(resident + nodes.BATCH_TEMP_MARGIN
              + left * 16 * nodes.ROW_BYTES)
        assert exmod.batch_chunk_size(rows, 16) == chunk, left


# -- the shard-axis bucket ---------------------------------------------------


@pytest.mark.parametrize("n_devices", [1, 4, 8])
def test_bucket_rule(n_devices):
    from pilosa_tpu.parallel.mesh_exec import MeshExecutor

    class Mesh:
        pass

    mesh = Mesh()
    mesh.n_devices = n_devices
    bucket = [MeshExecutor._bucket(mesh, n) for n in range(1025)]
    assert bucket[58] == 64 and bucket[64] == 64 and bucket[256] == 256
    assert bucket[172] <= 192
    if n_devices == 1:
        assert bucket[172] == 176 and bucket[954] == 960
    for n in range(1, 1025):
        assert bucket[n] >= n and bucket[n] % n_devices == 0
        assert bucket[n] >= bucket[n - 1]
        per_dev = -(-n // n_devices)
        # a device's share is padded by at most 8 shards or an eighth
        assert bucket[n] // n_devices - per_dev <= max(8, per_dev / 8), n
    # a one-shard difference does not recompile within a step
    assert len(set(bucket[1:])) <= 48


# -- the two layer metrics are data for the accepted reader -------------------


def _vars_ratio(name: str):
    _bench()
    from readers import vars_ratio
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "vars_ratio"
    return vars_ratio, spec


def _ctx(before: dict, after: dict) -> dict:
    return {"spans": {"trace": {"before": before, "after": after,
                                "n": 50}}}


def test_padded_shard_share_reads_the_two_counters():
    reader, spec = _vars_ratio("padded_shard_share")
    before = {"device": {"launches": {"launches": 10, "shardsStacked": 1760,
                                      "shardsPadded": 40}}}
    after = {"device": {"launches": {"launches": 60, "shardsStacked": 10560,
                                     "shardsPadded": 240}}}
    assert reader.read(spec, _ctx(before, after)) == \
        pytest.approx(100 * 4 / 176)
    bare = {"device": {"launches": {"launches": 60}}}
    assert reader.read(spec, _ctx(bare, bare)) is None


def test_temp_split_share_reads_the_two_counters():
    reader, spec = _vars_ratio("temp_split_share")
    before = {"batchTemp": {"boundBytes": 4 << 30, "splits": 3},
              "device": {"launches": {"launches": 10}}}
    after = {"batchTemp": {"boundBytes": 4 << 30, "splits": 5},
             "device": {"launches": {"launches": 60}}}
    assert reader.read(spec, _ctx(before, after)) == pytest.approx(4.0)
    bare = {"device": {"launches": {"launches": 60}}}
    assert reader.read(spec, _ctx(bare, bare)) is None


def test_the_cell_is_declared():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = bench["workloads"][3]
    assert cell == {**cell, "name": "ssb-q1-sf30.q1-flight",
                    "config": CONFIG, "traffic": MIX, "chips": 1}
    names = [w["name"] for w in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][17:19]] == [
        "padded_shard_share", "temp_split_share"]
    assert by_name["padded_shard_share"]["workloads"] == names
    # (and, appended by PR 37, the cell that launches under the bound
    # at 71 % of HBM: tests/test_taxi_1b_chip1.py)
    assert by_name["temp_split_share"]["workloads"][:2] == [
        "ssb.q1-flight", "ssb-q1-sf30.q1-flight"]
    # (the metrics past the twentieth are a later cell's own)
    assert all(cell["name"] in m["workloads"]
               for m in bench["per_layer"][:20])
    datagen = _bench()[0]
    small, large = (datagen.load_json("configs", c)
                    for c in ("ssb-q1-sf10", CONFIG))
    assert large["shards"] == 172
    moved = {k for k in small if small[k] != large[k]}
    assert moved == {"name", "source", "shards", "reduced_why"}
