"""The benchmark's traffic generator: a fixed population for every seed, drawn
from the published fits the mix names."""
import numpy as np
import pytest
from scipy import stats

from bench import gen, harness
from bench.tests.tiny import SEED

DEP = dict(tors=108, uplinks=1, slice_bytes=125_000, slice_us=10.0,
           packets=1 << 18)


@pytest.fixture(scope="module")
def mix():
    return harness.load_json("traffic", "kv_run")


@pytest.fixture(scope="module")
def pop(mix):
    return gen.population(DEP, mix)


def test_seeds_share_shapes_and_flow_count(mix, pop):
    a = gen.workload(DEP, mix, 0, pop)
    b = gen.workload(DEP, mix, SEED, pop)
    assert set(a) == set(b) == set(gen.FIELDS)
    for k in gen.FIELDS:
        assert a[k].shape == b[k].shape == (DEP["packets"],)
        assert a[k].dtype == b[k].dtype
    assert a["flow"].max() == b["flow"].max()
    assert not np.array_equal(a["src"], b["src"])
    # the same bytes in every slice, whatever the seed
    np.testing.assert_array_equal(np.bincount(a["t_inject"], a["size"]),
                                  np.bincount(b["t_inject"], b["size"]))
    # and the same per-ToR loads, only relabelled
    per_tor = lambda w: np.sort(np.bincount(w["src"], w["size"]))
    np.testing.assert_array_equal(per_tor(a), per_tor(b))


def test_same_seed_same_workload(mix, pop):
    a = gen.workload(DEP, mix, SEED, pop)
    b = gen.workload(DEP, mix, SEED)
    for k in gen.FIELDS:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_flows_are_paced_cells(mix, pop):
    w = gen.workload(DEP, mix, 5, pop)
    assert (w["src"] != w["dst"]).all()
    assert ((w["size"] > 0) & (w["size"] <= mix["cell_bytes"])).all()
    first = np.r_[True, w["flow"][1:] != w["flow"][:-1]]
    assert (w["seq"][first] == 0).all()
    assert (np.diff(w["seq"])[~first[1:]] == 1).all()
    per = DEP["slice_bytes"] // mix["cell_bytes"]
    start = np.repeat(w["t_inject"][first],
                      np.diff(np.r_[np.nonzero(first)[0], w["flow"].size]))
    np.testing.assert_array_equal(w["t_inject"], start + w["seq"] // per)


def test_draws_follow_the_published_fits(mix):
    """Key sizes, value sizes and gaps against scipy's own distributions
    with the mix's parameters (scipy's GEV shape is MATLAB's ``-k``)."""
    rng = np.random.default_rng(SEED)
    key, value = mix["flow_bytes"]
    mu, sigma, k = key["gev"]
    want = {"key": stats.genextreme(-k, loc=mu, scale=sigma).cdf,
            "value": stats.genpareto(value["gpareto"][2],
                                     loc=value["gpareto"][0],
                                     scale=value["gpareto"][1]).cdf}
    for part in (key, value):
        d = {k: v for k, v in part.items() if k != "clip"}
        x = gen.draw(rng, d, 200_000)
        assert stats.kstest(x, want[part["part"]]).statistic < 0.005
        assert x.mean() == pytest.approx(gen.mean(d), rel=0.03)
    theta, sigma, k = mix["interarrival_us"]["gpareto"]
    gaps = gen.draw(rng, mix["interarrival_us"], 200_000)
    assert stats.kstest(gaps, stats.genpareto(k, loc=theta,
                                              scale=sigma).cdf).statistic < 0.005


def test_offered_load_is_the_mixs(mix, pop):
    """Every whole slice of arrivals offers about ``load`` x the fabric's
    circuit capacity, and the population's sizes are key plus value."""
    w = gen.workload(DEP, mix, 7, pop)
    per_slice = np.bincount(w["t_inject"], w["size"])
    capacity = DEP["tors"] * DEP["uplinks"] * DEP["slice_bytes"]
    whole = per_slice[2:-2] / capacity
    assert whole.size >= 8
    assert np.abs(whole - mix["load"]).max() < 0.03
    assert gen.streams_per_tor(DEP, mix) == 260
    assert 300 < pop["size"].mean() < 430


def test_seeds_share_the_pool_up_to_a_shift(mix, pop):
    """Every seed's pool is the same workloads under cyclically shifted ToR
    labels, one shift per workload."""
    a = gen.pool(DEP, mix, 0, 4, pop)
    b = gen.pool(DEP, mix, SEED, 4, pop)
    n = DEP["tors"]
    shifts = []
    for wa, wb in zip(a, b):
        for k in set(gen.FIELDS) - {"src", "dst"}:
            np.testing.assert_array_equal(wa[k], wb[k], err_msg=k)
        c = (int(wb["src"][0]) - int(wa["src"][0])) % n
        for k in ("src", "dst"):
            assert wb[k].dtype == np.int32
            np.testing.assert_array_equal((wa[k] + c) % n, wb[k], err_msg=k)
        shifts.append(c)
    assert len(set(shifts)) > 1
    assert not np.array_equal(a[0]["dst"], a[1]["dst"])


@pytest.mark.parametrize("config", ["rotor108_vlb", "rotor108_ucmp"])
def test_a_shift_leaves_the_work_unchanged(config):
    """On the round-robin schedule the reference fabric gives every packet
    the same delivery slice, hops and losses under a shift of the ToR
    labels, and the per-slice totals are equal: a seed changes the names,
    not the work."""
    from bench import check
    from bench.reference import fabric_ref, tables_ref
    dep = harness.load_json("configs", config)
    dep.update(tors=8, packets=2048)
    mix = dict(harness.load_json("traffic", "kv_run"), num_slices=40)
    tables = tables_ref.deployment_tables(dep)
    cfg = check.reference_config(dep)
    a, b = gen.pool(dep, mix, 3, 1)[0], gen.pool(dep, mix, 4, 1)[0]
    assert not np.array_equal(a["src"], b["src"])
    ra = fabric_ref.simulate_ref(tables, a, cfg, 40)
    rb = fabric_ref.simulate_ref(tables, b, cfg, 40)
    for f in fabric_ref.RESULT_FIELDS:
        x, y = np.asarray(ra[f]), np.asarray(rb[f])
        if f in ("buf_bytes", "offl_bytes"):        # per node: relabelled
            x, y = np.sort(x, axis=-1), np.sort(y, axis=-1)
        np.testing.assert_array_equal(x, y, err_msg=f)
    assert (np.asarray(ra["t_deliver"]) >= 0).all()
