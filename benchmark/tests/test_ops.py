"""Each op's rendered PQL, answered by an in-process ``Executor`` on two
shards, equals the cube's answer — for every template of every mix — and
the control (a stale read) does not."""

import json
import os
import shutil
import tempfile

import pytest

import datagen
import loader
import oracle
import traffic

SHARDS = 2
CELLS = [("taxi-256", "topn-year-pcount"), ("taxi-256", "count-year-pcount"),
         ("ssb-q1-sf10", "q1-flight")]


@pytest.fixture(scope="module")
def loaded():
    """{config: (executor, {mix: cube})}, each configuration loaded once."""
    from pilosa_tpu.executor.executor import Executor
    from pilosa_tpu.storage.field import FieldOptions
    from pilosa_tpu.storage.holder import Holder
    tmp = tempfile.mkdtemp(prefix="ptpu-bench-test-")
    out, holders = {}, []
    for name in sorted({c for c, _ in CELLS}):
        cfg = datagen.load_json("configs", name)
        h = Holder(os.path.join(tmp, name))
        h.open()
        holders.append(h)
        idx = h.create_index(
            cfg["index"]["name"],
            track_existence=cfg["index"]["options"]["trackExistence"])
        for f in cfg["fields"]:
            idx.create_field(f["name"], FieldOptions(
                type="int", min=f["min"], max=f["max"])
                if f["type"] == "int" else FieldOptions())
        cubes = {}
        for c, m in CELLS:
            if c == name:
                cubes[m] = oracle.Cube(cfg, datagen.load_json("traffic", m))

        class Both:
            def shard_cells(self, cols):
                return {m: cube.shard_cells(cols)
                        for m, cube in cubes.items()}

            def add(self, cells, last=False):
                for m, cube in cubes.items():
                    cube.add(cells[m], last=last)

        loader.load(h, cfg, 77, SHARDS, Both())
        out[name] = (cfg, Executor(h), cubes)
    yield out
    for _, ex, _ in out.values():
        ex.close()
    for h in holders:
        h.close()
    shutil.rmtree(tmp, ignore_errors=True)


@pytest.mark.parametrize("config,mix_name", CELLS)
def test_every_template_equals_the_cube(loaded, config, mix_name):
    from pilosa_tpu.server.handler import serialize_result
    cfg, ex, cubes = loaded[config]
    mix = datagen.load_json("traffic", mix_name)
    reqs = traffic.Requests(cfg, mix, seed=2**31 + 5, per_client=12)
    seen, stale_wrong = set(), 0
    stale = cubes[mix_name].stale()
    for i, pql in enumerate(reqs.pql):
        got = json.loads(json.dumps(
            [serialize_result(r)
             for r in ex.execute(cfg["index"]["name"], pql)]))
        assert got == [reqs.expected(i, cubes[mix_name])], pql
        stale_wrong += got != [reqs.expected(i, stale)]
        seen.add(reqs.template[i])
    assert seen == set(range(len(mix["templates"])))
    # the control has to come out as not correct
    assert stale_wrong > 0


def test_least_bytes_counts_operands_once(loaded):
    cfg, _, _ = loaded["taxi-256"]
    mix = datagen.load_json("traffic", "topn-year-pcount")
    reqs = traffic.Requests(cfg, mix, seed=1, per_client=2)
    import numpy as np
    row_bytes = {f["name"]: np.full(f["rows"], 10) for f in cfg["fields"]}
    # 64 rows of dist_miles + one year row + one pcount row
    assert reqs.least_bytes(0, row_bytes) == 660
