"""``taxi-1b-chip1``: upstream's taxi example at its documented 954
shards on one node with ONE chip — more index than HBM, every option at
its default (benchmark/configs/taxi-1b-chip1.json, PERF.md PR 37) — held
here on the CPU at 10 shards to what the deployment forces:

* the device budget's limit at ``device-budget-mb``'s default is the
  device's own; an explicit value wins; no ``bytes_limit``, no limit;
* *does not fit*: behind a ``bytes_limit`` whose derived limit lies
  under the dense set, on a mesh of one device, both templates of the
  ``topn-amount-dist`` mix — alone, in bodies of 2 and 4 calls and as
  four concurrent requests — answer exactly what the plain reference's
  cube says from compressed stacks, in more than one shard slice, and a
  second pass of the same requests builds no executable, tail slice
  included;
* *fits*: nothing changes — dense forms, one slice, no fragment walked
  for a schedule, and the accepted cells' whole-query programs lower to
  the text they have with no limit at all;
* the configuration file, the cell's declaration, the two new layer
  metrics' files.
"""

import hashlib
import json
import os

import jax
import pytest

from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
from test_ssb_sf30 import BENCH, REPO, Deployment, _bench, _four_at_once

CONFIG, MIX = "taxi-1b-chip1", "topn-amount-dist"
CELL = "taxi-1b-chip1.topn-amount-dist"
SEED = 3700000037
SHARDS = 10
GIB, MIB = 1 << 30, 1 << 20
V5E_BYTES_LIMIT = 16909334528       # what a v5e reports (PERF.md §4)
# what the derived limit leaves a launch: the batch-temp ceiling and its
# margin (nodes.device_budget_bytes)
HELD_BACK = 4 * GIB + 1 * GIB
# 216 rows x 10 shards x 128 KiB = 283 MB dense; 224 MiB holds the
# compressed stacks (no eviction) and not the dense set
LIMIT = 224 * MIB
AMOUNT, DIST = 0, 1                 # the mix's templates


def _patched(mp, bytes_limit, one_device=False):
    """The device as ``nodes.device_bytes_limit`` would report it, and
    (``one_device``) the node's mesh cut to one device: one chip."""
    from pilosa_tpu.parallel import mesh_exec, nodes
    mp.setattr(nodes, "device_bytes_limit", lambda: bytes_limit)
    if one_device:
        real = mesh_exec.default_mesh
        mp.setattr(mesh_exec, "default_mesh",
                   lambda devices=None: real(jax.devices()[:1]))


@pytest.fixture(scope="module")
def dep():
    """The configuration at 10 shards behind a one-device ``Server`` at
    its defaults, but for the decode workspace: 64 MiB, so that a
    ``TopN(total_amount_dollars, ...)`` (18 MiB of tiles a shard; three
    shards a launch) is four slices, cut evenly: 3, 3, 2 and 2 shards,
    and the distance TopN two of 5."""
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
    old = (DEFAULT_BUDGET.limit_bytes, DEFAULT_BUDGET.limit_from_device,
           DEFAULT_BUDGET.spread)
    with pytest.MonkeyPatch.context() as mp:
        _patched(mp, HELD_BACK + LIMIT, one_device=True)
        mp.setenv("PILOSA_TPU_DECODE_WORKSPACE_MB", "64")
        d = Deployment(SHARDS, CONFIG, MIX, SEED)
        yield d
        d.close()
    (DEFAULT_BUDGET.limit_bytes, DEFAULT_BUDGET.limit_from_device,
     DEFAULT_BUDGET.spread) = old


def _compiles(v: dict) -> int:
    c = v["device"]["compiles"]
    return c["compiles"] + c["retraces"] + c["backendCompiles"]


# -- does not fit: the served path answers exactly -----------------------------


def test_the_limit_is_the_devices_and_the_set_does_not_fit(dep):
    budget = dep.vars()["deviceBudget"]
    assert budget["limitSource"] == "device"
    assert budget["limitBytes"] == LIMIT
    assert budget["denseDemandBytes"] >= 216 * SHARDS * (128 << 10)
    assert budget["denseDemandBytes"] * 9 // 8 > budget["limitBytes"]
    assert not DEFAULT_BUDGET.dense_fits()
    assert dep.srv.api.executor.mesh_exec.n_devices == 1


@pytest.mark.parametrize("size", [1, 2, 4])
@pytest.mark.parametrize("template", [AMOUNT, DIST],
                         ids=["amount", "dist"])
def test_topn_exact_from_compressed_slices(dep, template, size):
    """Requests of one template in bodies of ``size`` calls, twice: the
    second pass builds no executable, whatever slice it is in."""
    ids = dep.by_template[template]
    picks = [dep.pick(template, size, skip)
             for skip in range(0, min(len(ids), 3 * size), size)]
    v0 = dep.vars()
    for pick in picks:
        assert dep.client.query(dep.index, dep.body(pick)) == \
            dep.expected(pick), pick
    v1 = dep.vars()
    for pick in picks:
        assert dep.client.query(dep.index, dep.body(pick)) == \
            dep.expected(pick), pick
    v2 = dep.vars()
    assert _compiles(v2) == _compiles(v1)
    assert v1["wholeQuery"]["fallbacks"] > v0["wholeQuery"]["fallbacks"]
    # every request left the program; none was served by it (``requests``
    # counts those: the cell has no wq_fallback_share to read)
    assert v2["wholeQuery"]["requests"] == v0["wholeQuery"]["requests"]
    assert v2["wholeQuery"]["fallbacks"] - v0["wholeQuery"]["fallbacks"] \
        == 2 * len(picks)
    assert v1["wholeQuery"]["lastFallback"].startswith(
        "streamed-working-set")
    launches = v2["device"]["launches"]
    assert launches["decodeBytesTotal"] > \
        v1["device"]["launches"]["decodeBytesTotal"] > \
        v0["device"]["launches"]["decodeBytesTotal"]
    # more than one slice a request: 4 (amount) or 2 (dist) launches
    assert launches["launches"] - v1["device"]["launches"]["launches"] \
        >= 2 * len(picks)
    assert v2["deviceBudget"]["compressedBytes"] > 0
    assert v2["deviceBudget"]["evictions"] == v0["deviceBudget"]["evictions"]
    # the second pass planned its slices off the device epoch
    sc1, sc2 = v1["stackCache"], v2["stackCache"]
    assert sc2["scheduleWalks"] == sc1["scheduleWalks"]
    assert sc2["scheduleFastHits"] > sc1["scheduleFastHits"]


@pytest.mark.parametrize("template", [AMOUNT, DIST],
                         ids=["amount", "dist"])
def test_four_concurrent_requests_exact(dep, template):
    for got, want in _four_at_once(dep, template):
        assert got == want


def test_the_slices_and_forms_a_launch_ran_on(dep):
    """The launch ledger's last entries after one amount TopN: four
    slices cut evenly (a greedy cut leaves 3, 3, 3 and a tail of 1);
    the stacks: pickup_year dense by the density rule, the three sparse
    fields compressed."""
    from pilosa_tpu.utils import devobs
    pick = dep.pick(AMOUNT, 1)
    assert dep.client.query(dep.index, dep.body(pick)) == dep.expected(pick)
    n0 = devobs.LEDGER.launches_total
    assert dep.client.query(dep.index, dep.body(pick)) == dep.expected(pick)
    entries = devobs.LEDGER.snapshot()["entries"]
    last = entries[n0 - devobs.LEDGER.launches_total:]
    assert {e["kind"] for e in last} == {"row_counts"}
    # a slice is ONE launch: the shards' container and payload buckets
    # differ, and a key's compressed fragments are stacked at the
    # largest among them (``_group_sigs``)
    assert [(e["slice"], e["slices"], e["shards"]) for e in last] == \
        [(0, 4, 3), (1, 4, 3), (2, 4, 2), (3, 4, 2)]
    # a request decodes the ONE row its filter takes: the amount rows
    # are counted where they lie in the streams (fused_row_counts), by
    # XLA on the CPU (``z:jnp``: no kernel launch in the ledger)
    assert all(e["decodeBytes"] == e["shards"] * (128 << 10)
               and "kernelLaunches" not in e for e in last)
    pick = dep.pick(DIST, 1)
    assert dep.client.query(dep.index, dep.body(pick)) == dep.expected(pick)
    me = dep.srv.api.executor.mesh_exec
    with me._sc_lock:
        forms = {}
        for b in me._blocks.values():
            forms.setdefault(b.bkey[1][0], set()).add(b.token[0][0])
    assert forms == {"total_amount_dollars": {"z"}, "dist_miles": {"z"},
                     "passenger_count": {"z"}, "pickup_year": {8}}


def test_a_keys_compressed_fragments_are_one_shape_group():
    """Fragments of one key whose buckets differ (510 and 513
    containers; one with a run container) get one signature, the
    largest buckets; dense ones, absent ones and another row capacity
    stay as they are."""
    from pilosa_tpu.parallel.mesh_exec import MeshExecutor

    class Frag:
        def __init__(self, sig):
            self.sig = sig

    class Mesh:
        _group_sigs = MeshExecutor._group_sigs

        @staticmethod
        def _frag_sig(fr):
            return fr.sig

    def z(rows, c, p, a, r):
        return ("z", rows, c, p, a, r, "jnp")

    dense = (8, 256, 128)
    frags = [[Frag(z(64, 512, 393216, 1024, 0)), Frag(dense)],
             [Frag(z(64, 1024, 393216, 1024, 0)), None],
             [Frag(z(64, 512, 425984, 1024, 64)), Frag(dense)],
             [Frag(z(128, 2048, 1179648, 1024, 0)), Frag(dense)]]
    top = z(64, 1024, 425984, 1024, 64)
    assert Mesh()._group_sigs(frags, 2) == [
        (top, dense), (top, None), (top, dense),
        (z(128, 2048, 1179648, 1024, 0), dense)]


def test_launch_form_and_cost():
    """What the launch ledger and the ``form`` tag say of a launch
    follows what its body does: a row take of a compressed field costs a
    row a params row, a field counted in the packed stream decodes
    nothing and is one kernel launch where its signature names the
    kernel, and a launch of more params rows than a fused count takes
    decodes every compressed input whole."""
    from pilosa_tpu.executor.plan import NaryPlan, ReduceNode, RowPlan, Slot
    from pilosa_tpu.ops import kernels
    from pilosa_tpu.parallel.mesh_exec import launch_cost, launch_form
    tile = 128 << 10
    amount, pcount, year = (("amount", "standard"), ("pcount", "standard"),
                            ("year", "standard"))
    plan = NaryPlan("intersect", (RowPlan("year", ("standard",), Slot(0)),
                                  RowPlan("pcount", ("standard",), Slot(1))))
    topn = ReduceNode("row_counts", plan, amount, ())
    count = ReduceNode("count", plan, None, ())

    def layout(backend):
        return ((amount, 7, ("z", 128, 2048, 1 << 19, 35840, 0, backend)),
                (year, 1, (8, 256, 128)),
                (pcount, 7, ("z", 16, 256, 1 << 17, 3584, 0, backend)))

    for backend, form, kernel in (("pallas", "z:pallas", (1, 128 * 16)),
                                  ("jnp", "z:jnp", (0, 0))):
        lay = layout(backend)
        assert launch_form(lay, topn) == form
        assert launch_form(lay, count) == "z:jnp"
        assert launch_cost(lay, topn, 1) == (tile,) + kernel
        assert launch_cost(lay, topn, 4) == (4 * tile,) + kernel
        # past what a fused count unrolls: the decoding path
        assert launch_cost(lay, topn, 2 * kernels.FUSED_PARAMS_MAX) == (
            (128 + 2 * kernels.FUSED_PARAMS_MAX) * tile, 0, 0)
        # a count reads its plan's rows alone, of the fields it reads
        assert launch_cost(lay[1:], count, 2) == (2 * tile, 0, 0)
    assert launch_form(layout("pallas")[1:2], count) == "dense"
    assert launch_cost(layout("pallas")[1:2], count, 1) == (0, 0, 0)
    # no plan: nothing to take rows of, nothing decoded
    bare = ReduceNode("row_counts", None, amount, ())
    assert launch_cost(layout("jnp")[:1], bare, 1) == (0, 0, 0)


def test_form_and_slice_tags():
    from pilosa_tpu.parallel.mesh_exec import form_tag, slice_tags
    from pilosa_tpu.utils import devobs
    z = ("z", 128, 2048, 1 << 21, 1024, 0)
    assert form_tag([(8, 256, 128), None]) == "dense"
    assert form_tag([(8, 256, 128), z + ("jnp",)]) == "z:jnp"
    assert form_tag([z + ("pallas",), z + ("jnp",), z]) == "z:jnp+pallas"
    assert slice_tags() == {"slice": 0, "slices": 1}
    devobs.set_slice(2, 17)
    try:
        assert slice_tags() == {"slice": 2, "slices": 17}
    finally:
        devobs.set_slice(None)


# -- the derived limit -----------------------------------------------------------


@pytest.mark.parametrize("option_mb, bytes_limit, want", [
    (0, V5E_BYTES_LIMIT, (V5E_BYTES_LIMIT - HELD_BACK, "device")),
    (0, None, (None, "none")),
    (512, V5E_BYTES_LIMIT, (512 * MIB, "option")),
    (512, None, (512 * MIB, "option")),
    (0, 2 * GIB, (0, "device")),
], ids=["default", "no-bytes-limit", "option-wins", "option-on-cpu",
        "tiny-device"])
def test_the_derived_limit(tmp_path, monkeypatch, option_mb, bytes_limit,
                           want):
    from pilosa_tpu.server.server import Config, Server
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
    monkeypatch.setattr(DEFAULT_BUDGET, "limit_bytes", None)
    monkeypatch.setattr(DEFAULT_BUDGET, "limit_from_device", False)
    monkeypatch.setattr(DEFAULT_BUDGET, "spread", DEFAULT_BUDGET.spread)
    _patched(monkeypatch, bytes_limit)
    srv = Server(Config(data_dir=str(tmp_path / "d"), bind="localhost:0",
                        device_budget_mb=option_mb))
    srv.open()
    try:
        assert (DEFAULT_BUDGET.limit_bytes,
                DEFAULT_BUDGET.limit_source) == want
        assert DEFAULT_BUDGET.stats()["limitSource"] == want[1]
    finally:
        srv.close()


# -- fits: nothing changes --------------------------------------------------------


class Lowered(Deployment):
    """A deployment that keeps the lowered text's sha256 of every
    whole-query program it launches (``_InstrumentedWhole.temp_bytes``
    sees each program once, with its arguments)."""

    def __init__(self, mp, *args):
        from pilosa_tpu.parallel import wholequery
        self.shas: list = []
        read = wholequery._InstrumentedWhole.temp_bytes

        def spy(fn, local, mats, flat):
            if local not in fn._temps:
                text = fn.fn.lower(mats, *flat).as_text()
                self.shas.append(hashlib.sha256(text.encode()).hexdigest())
            return read(fn, local, mats, flat)

        mp.setattr(wholequery._InstrumentedWhole, "temp_bytes", spy)
        super().__init__(*args)

    def ask_all(self):
        for template in sorted(self.by_template):
            for size in (1, 2):
                pick = self.pick(template, size)
                assert self.client.query(self.index, self.body(pick)) == \
                    self.expected(pick)


@pytest.mark.parametrize("config, mix", [
    ("taxi-256", "topn-year-pcount"), ("ssb-q1-sf10", "q1-flight")])
def test_a_set_that_fits_runs_what_it_ran(monkeypatch, config, mix):
    """With the v5e's ``bytes_limit`` behind the default, an accepted
    cell's configuration at 3 shards: the limit is the device's, every
    form dense, one slice, nothing staged on the host, no fragment
    walked for a schedule — and every whole-query program lowers to the
    text it has with no limit at all."""
    from pilosa_tpu.parallel.mesh_exec import MeshExecutor
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET, \
        HOST_STAGE_BUDGET
    for attr in ("limit_bytes", "limit_from_device", "spread"):
        monkeypatch.setattr(DEFAULT_BUDGET, attr,
                            getattr(DEFAULT_BUDGET, attr))
    _patched(monkeypatch, None)         # as on the CPU: no limit at all
    plain = Lowered(monkeypatch, 3, config, mix, SEED)
    try:
        plain.ask_all()
        assert plain.vars()["deviceBudget"]["limitSource"] == "none"
        want = list(plain.shas)     # its spy stays on beneath the next
    finally:
        plain.close()
    _patched(monkeypatch, V5E_BYTES_LIMIT)

    def walked(*a, **k):
        raise AssertionError("a schedule walked the fragments")

    monkeypatch.setattr(MeshExecutor, "_estimate_shard_bytes", walked)
    # the budget is the process's: another deployment's blocks may lie
    # in it, so what this one adds is read as a difference
    before = DEFAULT_BUDGET.stats()
    staged = HOST_STAGE_BUDGET.resident_bytes
    limited = Lowered(monkeypatch, 3, config, mix, SEED)
    try:
        limited.ask_all()
        limited.ask_all()
        v = limited.vars()
        budget = v["deviceBudget"]
        assert budget["limitSource"] == "device"
        assert budget["limitBytes"] == V5E_BYTES_LIMIT - HELD_BACK
        assert budget["denseDemandBytes"] * 9 // 8 <= budget["limitBytes"]
        assert DEFAULT_BUDGET.dense_fits()
        assert budget["compressedBytes"] == before["compressedBytes"]
        assert budget["evictions"] == before["evictions"]
        assert budget["residentBytes"] > before["residentBytes"]
        assert v["hostStage"]["residentBytes"] == staged
        assert v["wholeQuery"]["fallbacks"] == 0
        assert v["stackCache"]["scheduleWalks"] == 0 == \
            v["stackCache"]["scheduleFastHits"]
        me = limited.srv.api.executor.mesh_exec
        with me._sc_lock:
            assert all(b.compressed == 0 and b.token[0][0] != "z"
                       for b in me._blocks.values())
        assert v["batchTemp"]["boundBytes"] == 4 * GIB
    finally:
        limited.close()
    assert want and limited.shas == want


# -- the files --------------------------------------------------------------------


def test_the_configuration_is_the_source_at_its_scale():
    datagen = _bench()[0]
    cut, mesh4, chip1 = (datagen.load_json("configs", c)
                         for c in ("taxi-256", "taxi-1b-mesh4", CONFIG))
    assert chip1["shards"] == 954 == chip1["published"]["shards"]
    assert chip1["published"] == mesh4["published"]
    assert chip1["reduced"] == ["fields"] == list(chip1["reduced_why"])
    assert set(chip1) == set(mesh4)
    assert chip1["guarantees"] == cut["guarantees"]
    assert chip1["index"] == cut["index"]
    assert "one TPU v5e chip" in chip1["deployment"]
    assert len(chip1["source"]) <= 200
    by_name = {c["name"]: c for c in cut["columns"]}
    fields = {f["name"]: f for f in cut["fields"]}
    kept = ["passenger_count", "pickup_year", "dist_miles"]
    assert chip1["columns"][:3] == [by_name[n] for n in kept]
    assert chip1["fields"][:3] == [fields[n] for n in kept]
    assert chip1["columns"][3] == {
        "name": "total_amount_dollars",
        "draw": {"kind": "geometric", "ratio": 0.93, "n": 128}}
    assert chip1["fields"][3] == {
        "name": "total_amount_dollars", "type": "set", "rows": 128,
        "column": "total_amount_dollars"}
    assert set(chip1["assumed"]) == set(cut["assumed"]) | {
        "total_amount_clip"}
    # dense at _cap_rows, 960 stacked shards: 1.61 x a v5e's bytes_limit
    dense = (16 + 8 + 64 + 128) * 960 * (128 << 10)
    assert dense == 27179089920 > 1.6 * V5E_BYTES_LIMIT


def test_the_mix_is_the_documented_queries():
    datagen, _, _, _, traffic = _bench()
    mix = datagen.load_json("traffic", MIX)
    old = datagen.load_json("traffic", "topn-year-pcount")
    assert (mix["loop"], mix["clients"], mix["processes"]) == \
        ("closed", 4, 4)
    assert mix["cube"]["axes"] == ["pickup_year", "passenger_count",
                                   "dist_miles", "total_amount_dollars"]
    amount, dist = mix["templates"]
    assert dist == old["templates"][0]
    assert (amount["op"], amount["field"], amount["n"], amount["weight"]) \
        == ("topn", "total_amount_dollars", 128, 1)
    requests = traffic.Requests(datagen.load_json("configs", CONFIG), mix,
                                SEED, per_client=8)
    assert "TopN(total_amount_dollars, Row(passenger_count=1), n=128)" \
        in requests.pql


def test_the_cell_is_declared():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": MIX, "chips": 1}
    assert len(cell["why"]) <= 200
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config == {**config, "reduced": ["fields"],
                      "file": f"benchmark/configs/{CONFIG}.json"}
    datagen = _bench()[0]
    assert config["source"] == datagen.load_json("configs", CONFIG)["source"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-2:] == ["decode_bytes_per_query", "evictions_per_query"]
    taxi = "taxi.topn-year-pcount"
    listed = {n for n, m in by_name.items() if CELL in m["workloads"]}
    counters = {n for n, m in by_name.items()
                if m["source"] == "program_counter"
                and taxi in m["workloads"]}
    # wq_fallback_share divides by requests the program served, and
    # scatter_ms_per_query reads the fused launch's scatter: a streamed
    # set has neither; temp_split_share is the SSB cells' and this
    # one's, which launches under the batch-temp bound at 71 % of HBM
    assert listed == (counters - {"topn_rows_read_share",
                                  "wq_fallback_share",
                                  "scatter_ms_per_query"}) | {
        "device_idle_share", "kernels_roofline", "temp_split_share",
        "decode_bytes_per_query", "evictions_per_query"}
    assert "compiles_in_window" in listed
    for name, layer, unit in (
            ("decode_bytes_per_query", "kernels", "bytes/query"),
            ("evictions_per_query", "stack and place", "evictions/query")):
        m = by_name[name]
        assert m == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": layer,
                     "moves": "qps", "workloads": [CELL]}


@pytest.mark.parametrize("metric, path, moved, want", [
    ("decode_bytes_per_query", ("device", "launches", "decodeBytesTotal"),
     17 * (1 << 30), 17 * (1 << 30) / 8),
    ("evictions_per_query", ("deviceBudget", "evictions"), 4, 0.5),
])
def test_the_new_metrics_read_their_counters(metric, path, moved, want):
    import importlib
    _bench()
    with open(os.path.join(BENCH, "layer_metrics", f"{metric}.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "vars_ratio" and spec["span"] == "trace"
    reader = importlib.import_module("readers.vars_ratio")

    def snapshot(value):
        out = node = {}
        for k in path[:-1]:
            node[k] = node = {}
        node[path[-1]] = value
        return out

    def ctx(before, after, n):
        return {"spans": {"trace": {"before": before, "after": after,
                                    "n": n}}}

    assert reader.read(spec, ctx(snapshot(100), snapshot(100 + moved), 8)) \
        == want
    assert reader.read(spec, ctx(snapshot(7), snapshot(7), 8)) == 0.0
    # no request completed in the span, or a program without the
    # counter (the parent of the PR that adds one): nothing, no raise
    assert reader.read(spec, ctx(snapshot(0), snapshot(moved), 0)) is None
    assert reader.read(spec, ctx({}, {}, 8)) is None
