"""The benchmark's own yardstick: Rand index, byte and operation counts,
generators, and the float64 reference of a job."""
from __future__ import annotations

import itertools

import numpy as np
import pytest

from bench import cost, reference, traffic
from bench.spec import Spec


def brute_rand(a, b):
    n = len(a)
    agree = sum((a[i] == a[j]) == (b[i] == b[j])
                for i, j in itertools.combinations(range(n), 2))
    return agree / (n * (n - 1) // 2)


@pytest.mark.parametrize("seed,n,k", [(0, 40, 3), (1, 57, 5), (2, 2, 2),
                                      (3, 90, 10)])
def test_rand_index_equals_pair_count(seed, n, k):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, k, n)
    b = np.where(rng.random(n) < 0.7, a, rng.integers(0, k, n))
    assert reference.rand_index(a, b, k) == pytest.approx(brute_rand(a, b),
                                                          abs=1e-15)


def test_rand_index_of_a_relabelled_partition_is_one():
    a = np.array([0, 0, 1, 1, 2, 2, 2])
    assert reference.rand_index(a, (a + 1) % 3, 3) == 1.0


def test_job_bytes_by_hand():
    # poker: 1,025,010 x 11 f32 points, K = 10, 88 sweeps: 10 seeding passes
    # + 88 sweeps + 1 labels pass over 45,100,440 bytes, + the int32 labels
    n, d, k, it = 1_025_010, 11, 10, 88
    assert cost.job_bytes(n, d, k, it) == 99 * 45_100_440 + 4_100_040


def test_job_flops_by_hand():
    n, d, k, it = 100, 3, 6, 4
    seeding = 6 * 3 * 100 * 3
    sweeps = 5 * (2 * 100 * 3 * 6 + 3 * 100 * 6)
    assert cost.job_flops(n, d, k, it) == seeding + sweeps


def test_roofline_bound_is_bandwidth_at_small_k():
    peak = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    n, d, k, it = 1_025_010, 11, 10, 88
    least, bound = cost.roofline_s(cost.job_bytes(n, d, k, it),
                                   cost.job_flops(n, d, k, it), peak)
    assert bound == "hbm"
    assert least == pytest.approx(cost.job_bytes(n, d, k, it) / 819e9)


def dataset(data, seed):
    return Spec().generator(data["generator"])(data, seed)


@pytest.mark.parametrize("data", [
    {"generator": "poker", "n": 500, "d": 11},
    {"generator": "spacenet_image", "image_shape": [20, 30, 3], "k_true": 6},
])
def test_generators_are_deterministic_in_the_seed(data):
    a, b, c = (dataset(data, s) for s in (5, 5, 6))
    assert a.dtype == np.float32
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_random_groups_are_deterministic_and_disjoint():
    x = np.arange(50, dtype=np.float32).reshape(25, 2)
    g1 = traffic.random_groups(x, 6, seed=3)
    g2 = traffic.random_groups(x, 6, seed=3)
    np.testing.assert_array_equal(g1, g2)
    assert g1.shape == (4, 6, 2)
    rows = g1.reshape(-1, 2)[:, 0]
    assert len(set(rows.tolist())) == 24


def test_unknown_generator_raises():
    with pytest.raises(KeyError):
        dataset({"generator": "nope"}, 0)


def plain_lloyd(x, c, iters):
    """Lloyd by direct differences, for a fixed number of sweeps."""
    for _ in range(iters):
        d2 = ((x[:, None, :] - c[None]) ** 2).sum(-1)
        lab = d2.argmin(1)
        c = np.stack([x[lab == q].mean(0) if np.any(lab == q) else c[q]
                      for q in range(c.shape[0])])
    d2 = ((x[:, None, :] - c[None]) ** 2).sum(-1)
    return d2.argmin(1), d2.min(1).sum(), c


def jittered(seed):
    """Poker rows off their integer grid, so no two distances tie."""
    x = dataset({"generator": "poker", "n": 400, "d": 11}, seed)
    return x + np.random.default_rng(seed).normal(0, 0.01, x.shape)


def test_reference_full_run_is_the_lloyd_fixed_point():
    x = jittered(1)
    c0 = x[:4].copy() + 0.1
    early, full = reference.lloyd(x, c0, h_star=1e-12, patience=3,
                                  max_iters=300, full_max_iters=900)
    lab, j, c = plain_lloyd(x, c0, full.n_iters)
    np.testing.assert_array_equal(full.labels, lab)
    np.testing.assert_allclose(full.centroids, c, rtol=1e-12)
    assert full.objective == pytest.approx(j, rel=1e-12)
    assert early.n_iters <= full.n_iters


def test_reference_early_stop_follows_eq7():
    x = jittered(2)
    c0 = x[:5].copy()
    early, full = reference.lloyd(x, c0, h_star=1e-2, patience=1,
                                  max_iters=300, full_max_iters=900)
    # the stop needs two objectives to form h, so it comes at sweep 2 or
    # later, and a threshold this loose stops well before the fixed point
    assert 2 <= early.n_iters < full.n_iters
    lab, _, c = plain_lloyd(x, c0, early.n_iters)
    np.testing.assert_array_equal(early.labels, lab)
    np.testing.assert_allclose(early.centroids, c, rtol=1e-12)


def test_seeding_follows_the_programs_key_schedule():
    import jax
    import jax.numpy as jnp
    from repro.core.kmeans import kmeans_plus_plus_init
    x = dataset({"generator": "poker", "n": 3000, "d": 11}, 4)
    for seed in (0, 7, 123):
        got = np.asarray(kmeans_plus_plus_init(jax.random.PRNGKey(seed),
                                               jnp.asarray(x), 10))
        ref = reference.kmeans_pp(seed, x.astype(np.float64), 10)
        np.testing.assert_array_equal(got, ref.astype(np.float32))


def test_self_consistency_finds_a_wrong_label():
    x = np.array([[0.0, 0.0], [0.1, 0.0], [5.0, 5.0], [5.1, 5.0]])
    c = np.array([[0.05, 0.0], [5.05, 5.0]])
    labels = np.array([0, 0, 1, 1])
    j = float(((x - c[labels]) ** 2).sum())
    good = reference.Fit(labels, j, 3, c)
    excess, j_gap = reference.self_consistency(x, good)
    assert excess == 0.0 and j_gap < 1e-12
    bad = good._replace(labels=np.array([0, 1, 1, 1]))
    excess, j_gap = reference.self_consistency(x, bad)
    assert excess > 0.1 and j_gap > 0.1
