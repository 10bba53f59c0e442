"""Seeded inputs: the same seed gives the same inputs and the same work."""
import numpy as np
import pytest

from bench import common, registry

SEEDS = [0, 7, 2 ** 31 + 3, 2 ** 40 + 1, -5]


@pytest.fixture(scope="module")
def steady():
    return registry.Cell("paper-steady-256")


@pytest.fixture(scope="module")
def ap():
    return registry.Cell("paper-ap-2e20")


@pytest.mark.parametrize("seed", SEEDS)
def test_power_maps_repeat_and_hold_the_papers_power(steady, seed):
    from bench.reference import paper
    gen = steady.job_module.power_map
    cfg = steady.config
    for n in (32, 192):
        tr = dict(steady.traffic, die_cells=n)
        a = gen(cfg, tr, seed, 3)
        assert np.array_equal(a, gen(cfg, tr, seed, 3))
        assert not np.array_equal(a, gen(cfg, tr, seed, 4))
        assert (a > 0).all() and a.shape == (6, n, n)
        layer_W = paper.ap_power_W(cfg, cfg["models"]["n_data"])
        leak_W = cfg["models"]["gamma_W_mm2"] * paper.ap_die_w_mm(cfg) ** 2
        lo, hi = tr["bank_activity"]
        for layer in a[2:6]:
            dyn = layer.sum() - leak_W
            assert lo * (layer_W - leak_W) * 0.999 <= dyn
            assert dyn <= hi * (layer_W - leak_W) * 1.001


@pytest.mark.parametrize("seed", SEEDS)
def test_sweep_jobs_repeat_and_vary_only_the_sink(seed):
    sweep = registry.Cell("paper-sweep-64-simd")
    r = sweep.job_module.r_convec
    a = r(sweep.config, sweep.traffic, seed, 5)
    assert a == r(sweep.config, sweep.traffic, seed, 5)
    assert a != r(sweep.config, sweep.traffic, seed, 6)
    assert abs(a / sweep.config["package"]["r_convec_K_W"] - 1) <= 0.1


@pytest.mark.parametrize("seed", SEEDS)
def test_ap_operands_repeat(ap, seed):
    ops = ap.job_module.operands
    a = ops("mac8", 256, seed, 2, 1)
    b = ops("mac8", 256, seed, 2, 1)
    assert all(np.array_equal(a[k], b[k]) for k in ("a", "b", "acc"))
    assert int(a["a"].max()) < 256 and int(a["acc"].max()) < 1 << 16


@pytest.mark.parametrize("seed", SEEDS)
def test_every_job_runs_every_program_once(ap, seed):
    orders = [ap.job_module.order_of(ap.traffic, seed, i) for i in range(8)]
    for order in orders:
        assert sorted(order) == sorted(ap.traffic["programs"])
    assert orders == [ap.job_module.order_of(ap.traffic, seed, i)
                      for i in range(8)]


def test_seeds_wider_than_64_bits_fold():
    a = common.rng(2 ** 64 + 5, 1).integers(0, 1 << 30)
    b = common.rng(5, 1).integers(0, 1 << 30)
    assert a == b


def test_percentile_is_inclusive():
    assert common.percentile(range(1, 102), 95) == pytest.approx(96.0)
    assert common.percentile([4.0], 95) == 4.0
