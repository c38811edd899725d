"""The generators' marginals against the configurations' weights, and the
seed's reach."""

import numpy as np
import pytest

import datagen

SHARDS = 4


@pytest.mark.parametrize("name", ["taxi-256", "ssb-q1-sf10"])
def test_marginals_within_one_percent(name):
    cfg = datagen.load_json("configs", name)
    cols = [datagen.shard_columns(cfg, 2**31 + 11, s) for s in range(SHARDS)]
    for c in cfg["columns"]:
        if "draw" not in c:
            continue
        w = datagen.draw_weights(c["draw"])
        lo = int(c["draw"].get("lo", 0))
        v = np.concatenate([x[c["name"]] for x in cols]) - lo
        assert v.min() >= 0 and v.max() < w.size
        got = np.bincount(v, minlength=w.size) / v.size
        # within 1 % of the weight, or of six standard errors for rare values
        tol = np.maximum(0.01 * w, 6 * np.sqrt(w / v.size))
        assert (np.abs(got - w) <= tol).all(), c["name"]


def test_same_seed_same_columns_other_seed_other_columns():
    cfg = datagen.load_json("configs", "taxi-256")
    a = datagen.shard_columns(cfg, 3_000_000_019, 1)
    b = datagen.shard_columns(cfg, 3_000_000_019, 1)
    c = datagen.shard_columns(cfg, 3_000_000_020, 1)
    assert all((a[k] == b[k]).all() for k in a)
    assert any((a[k] != c[k]).any() for k in a)


def test_ssb_product_fits_its_field():
    cfg = datagen.load_json("configs", "ssb-q1-sf10")
    cols = datagen.shard_columns(cfg, 5, 0)
    f = next(f for f in cfg["fields"] if f["name"] == "lo_extdisc")
    assert cols["lo_extdisc"].min() >= f["min"]
    assert cols["lo_extdisc"].max() <= f["max"]
    assert cols["d_year"].max() == 6 and cols["d_yearmonthnum"].max() == 79
    assert cols["d_weeknuminyear"].max() == 52


def test_every_stretch_of_a_client_holds_the_mix():
    import traffic
    cfg = datagen.load_json("configs", "ssb-q1-sf10")
    mix = datagen.load_json("traffic", "q1-flight")
    for seed in (1, 2**31 + 7):
        reqs = traffic.Requests(cfg, mix, seed, per_client=240)
        for seq in reqs.sequences:
            t = np.asarray([reqs.template[i] for i in seq])
            for start in range(0, 240, 12):
                assert np.bincount(t[start:start + 12], minlength=3) \
                    .tolist() == [4, 4, 4]
