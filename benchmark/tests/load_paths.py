#!/usr/bin/env python3
"""Times the candidate load paths on this host, per shard, for each
configuration: per-row ``Fragment.set_row``; one ``_or_words`` and one
snapshot per fragment (``loader.fill_fragment``, the one kept); the
fields' own bulk imports (``Field.import_bits`` / ``import_values``).  All
three leave the files a restarted server would open.  Host code only; run
it where the benchmark runs:

    python benchmark/tests/load_paths.py [shards]
"""

import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

import datagen
import loader


def fresh_holder(tmp: str, cfg: dict):
    from pilosa_tpu.storage.field import FieldOptions
    from pilosa_tpu.storage.holder import Holder
    h = Holder(tmp)
    h.open()
    idx = h.create_index(cfg["index"]["name"], track_existence=False)
    for f in cfg["fields"]:
        opts = FieldOptions(type="int", min=f["min"], max=f["max"]) \
            if f["type"] == "int" else FieldOptions()
        idx.create_field(f["name"], opts)
    return h, idx


def view_of(idx, f: dict):
    field = idx.field(f["name"])
    if f["type"] == "int":
        field.options.bit_depth = loader.field_depth(f)
        return field._create_view_if_not_exists(field.bsi_view_name())
    return field._create_view_if_not_exists("standard")


def main() -> int:
    shards = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    for name in ("taxi-256", "ssb-q1-sf10"):
        cfg = datagen.load_json("configs", name)
        t = time.monotonic()
        cols = [datagen.shard_columns(cfg, 7, s) for s in range(shards)]
        gen_s = (time.monotonic() - t) / shards
        t = time.monotonic()
        blocks = [{f["name"]: loader.field_block(f, c[f["column"]])
                   for f in cfg["fields"]} for c in cols]
        block_s = (time.monotonic() - t) / shards
        out = {"config": name, "shards": shards, "generate_s": gen_s,
               "bit_rows_s": block_s}
        for method in ("set_row", "or_words_snapshot", "field_import"):
            tmp = tempfile.mkdtemp(prefix="ptpu-loadpaths-")
            try:
                h, idx = fresh_holder(tmp, cfg)
                t = time.monotonic()
                for s in range(shards):
                    for f in cfg["fields"]:
                        block = blocks[s][f["name"]]
                        if method == "field_import":
                            col = cols[s][f["column"]].astype(np.int64)
                            ids = np.arange(col.size, dtype=np.int64) \
                                + (s << 20)
                            if f["type"] == "int":
                                idx.field(f["name"]).import_values(ids, col)
                            else:
                                idx.field(f["name"]).import_bits(col, ids)
                            continue
                        frag = view_of(idx, f) \
                            .create_fragment_if_not_exists(s)
                        if method == "set_row":
                            for r in range(block.shape[0]):
                                frag.set_row(r, block[r])
                        else:
                            loader.fill_fragment(frag, block)
                out[method + "_s"] = (time.monotonic() - t) / shards
                h.close()
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
        print(out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
