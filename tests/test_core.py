"""BSO-SL core unit tests: distribution stats, k-means, brain storm,
cluster aggregation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import cluster_fedavg, fedavg
from repro.core.bso import brain_storm
from repro.core.diststats import (full_params_bytes, param_distribution,
                                  swarm_distribution_matrix,
                                  swarm_distribution_matrix_loop,
                                  upload_bytes)
from repro.core.kmeans import assign, kmeans, lloyd_step

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------- diststats

def test_param_distribution_deterministic_order():
    p = {"b": jnp.ones((3, 3)), "a": jnp.zeros((5,)),
         "c": {"x": jnp.full((2,), 2.0)}}
    f1 = param_distribution(p)
    f2 = param_distribution({"c": {"x": jnp.full((2,), 2.0)},
                             "a": jnp.zeros((5,)), "b": jnp.ones((3, 3))})
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    # a: mean 0 var 0; b: mean 1 var 0; c/x: mean 2 var 0
    np.testing.assert_allclose(np.asarray(f1),
                               [0, 0, 1, 0, 2, 0], atol=1e-7)


def test_upload_bytes_is_tiny_vs_full_params():
    p = {"w1": jnp.zeros((256, 256)), "w2": jnp.zeros((1024,))}
    assert upload_bytes(p) == 2 * 2 * 4
    assert full_params_bytes(p) == (256 * 256 + 1024) * 4
    assert upload_bytes(p) < full_params_bytes(p) / 1000


def test_swarm_distribution_matrix_batched_matches_loop():
    """New-vs-old parity at N=8: the single-pass batched coordinator
    path equals the per-client host loop (jnp and Pallas flavours)."""
    n = 8
    ks = jax.random.split(KEY, 3)
    stacked = {"w": jax.random.normal(ks[0], (n, 5, 3)) * 3.0 + 1.0,
               "nested": {"b": jax.random.normal(ks[1], (n, 7))},
               "step": jnp.zeros((n,), jnp.int32)}        # non-float: skipped
    old = swarm_distribution_matrix_loop(stacked, n)
    new = swarm_distribution_matrix(stacked, n)
    assert new.shape == old.shape == (n, 4)
    np.testing.assert_allclose(np.asarray(new), np.asarray(old),
                               rtol=1e-5, atol=1e-6)
    new_pl = swarm_distribution_matrix(stacked, n, use_pallas=True)
    np.testing.assert_allclose(np.asarray(new_pl), np.asarray(old),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------------ kmeans

def test_kmeans_separates_obvious_clusters():
    a = jax.random.normal(KEY, (10, 4)) * 0.1
    b = jax.random.normal(jax.random.PRNGKey(1), (10, 4)) * 0.1 + 10.0
    X = jnp.concatenate([a, b])
    _, assignments = kmeans(KEY, X, 2, iters=10)
    a_ids = set(np.asarray(assignments[:10]).tolist())
    b_ids = set(np.asarray(assignments[10:]).tolist())
    assert len(a_ids) == 1 and len(b_ids) == 1 and a_ids != b_ids


def test_kmeans_assign_is_nearest():
    X = jax.random.normal(KEY, (20, 3))
    C = jax.random.normal(jax.random.PRNGKey(1), (4, 3))
    a = assign(X, C)
    d = jnp.sum((X[:, None, :] - C[None]) ** 2, axis=-1)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(jnp.argmin(d, 1)))


def test_kmeans_no_empty_clusters_with_enough_points():
    X = jax.random.normal(KEY, (14, 6))
    _, a = kmeans(KEY, X, 3, iters=30)
    assert len(set(np.asarray(a).tolist())) == 3


def test_kmeans_empty_clusters_reseed_to_distinct_points():
    """Two empty clusters must take two *different* far points (the old
    reseed gave every empty cluster the same farthest point, leaving
    duplicate centroids that can never separate)."""
    X = jnp.asarray([[0.0], [1.0], [10.0], [11.0], [20.0], [21.0]])
    C = jnp.asarray([[0.5], [100.0], [200.0]])   # clusters 1 and 2 empty
    newC = np.asarray(lloyd_step(X, C, 3))
    assert newC[1, 0] != newC[2, 0]
    assert {newC[1, 0], newC[2, 0]} <= set(np.asarray(X)[:, 0].tolist())
    # the farthest two points from the only live centroid
    assert {newC[1, 0], newC[2, 0]} == {21.0, 20.0}


def test_kmeans_pallas_path_matches_jnp():
    X = jax.random.normal(KEY, (40, 6))
    C1, a1 = kmeans(KEY, X, 3, iters=8)
    C2, a2 = kmeans(KEY, X, 3, iters=8, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_allclose(np.asarray(C1), np.asarray(C2),
                               rtol=1e-6, atol=1e-6)


# -------------------------------------------------------------- brain storm

def _plan(seed, p1, p2, val=None, assignments=None, k=3, n=14):
    rng = np.random.default_rng(seed)
    val = np.linspace(0, 1, n) if val is None else val
    assignments = np.arange(n) % k if assignments is None else assignments
    return brain_storm(rng, assignments, val, k, p1, p2)


def test_centers_are_best_val_members_when_no_disruption():
    # p1 = p2 = 1.0 => r > p never fires: pure center selection
    plan = _plan(0, 1.0, 1.0)
    for c in range(3):
        members = np.where(plan.assignments == c)[0]
        best = members[np.argmax(np.linspace(0, 1, 14)[members])]
        assert plan.centers[c] == best
    assert plan.events == []


def test_replacement_fires_with_p1_zero():
    plan = _plan(3, 0.0, 1.0)
    # every cluster's center replaced by a random member (still a member)
    for c in range(3):
        members = set(np.where(plan.assignments == c)[0].tolist())
        assert int(plan.centers[c]) in members


def test_swap_exchanges_cluster_membership():
    plan = _plan(5, 1.0, 0.0)   # swaps fire every cluster
    assert any("swap" in e for e in plan.events)
    # assignments remain a permutation-consistent partition of clients
    assert sorted(np.unique(plan.assignments).tolist()) == [0, 1, 2] or \
        len(np.unique(plan.assignments)) <= 3


def test_paper_probabilities():
    """p1=0.9/p2=0.8 with r>p trigger => ~10% / ~20% event rates."""
    n_rep, n_swap = 0, 0
    trials = 2000
    for s in range(trials):
        plan = _plan(s, 0.9, 0.8)
        n_rep += sum("replace" in e for e in plan.events)
        n_swap += sum("swap" in e for e in plan.events)
    rep_rate = n_rep / (trials * 3)
    swap_rate = n_swap / (trials * 3)
    assert 0.05 < rep_rate < 0.15, rep_rate        # ~0.1 (minus no-op draws)
    assert 0.10 < swap_rate < 0.30, swap_rate      # ~0.2
    # swaps are pairwise: both clusters record one event jointly => the
    # per-cluster *initiation* rate is what we bound


def test_brain_storm_assignments_are_a_relabeling():
    """For any (p1, p2): post-swap assignments are the same multiset of
    cluster labels (swaps exchange membership, never create/destroy),
    and every center is a member of its post-swap cluster."""
    for seed in range(30):
        rng = np.random.default_rng(seed)
        n, k = 14, 3
        a0 = rng.integers(0, k, size=n)
        val = rng.uniform(size=n).astype(np.float32)
        p1, p2 = rng.uniform(), rng.uniform()
        plan = brain_storm(rng, a0.copy(), val, k, p1, p2)
        assert sorted(plan.assignments.tolist()) == sorted(a0.tolist())
        for c in range(k):
            if plan.centers[c] >= 0:
                assert plan.assignments[plan.centers[c]] == c


def test_brain_storm_p1_p2_one_is_noop():
    """p1 = p2 = 1.0 => r > p never fires: assignments untouched, no
    events, centers are the per-cluster best-validation members."""
    for seed in range(10):
        rng = np.random.default_rng(seed)
        n, k = 14, 3
        a0 = rng.integers(0, k, size=n)
        val = rng.uniform(size=n).astype(np.float32)
        plan = brain_storm(rng, a0.copy(), val, k, 1.0, 1.0)
        np.testing.assert_array_equal(plan.assignments, a0)
        assert plan.events == []
        for c in range(k):
            members = np.where(a0 == c)[0]
            if len(members):
                assert plan.centers[c] == members[np.argmax(val[members])]


# ------------------------------------------------------------- aggregation

def _tree(x):
    return {"w": jnp.asarray(x, jnp.float32), "b": jnp.asarray([x[0]], jnp.float32)}


def test_fedavg_weighted_mean():
    t1, t2 = _tree([1.0, 2.0]), _tree([3.0, 6.0])
    out = fedavg([t1, t2], [1.0, 3.0])
    np.testing.assert_allclose(np.asarray(out["w"]), [2.5, 5.0])


def test_cluster_fedavg_matches_manual():
    stacked = {"w": jnp.asarray([[1.0], [3.0], [10.0], [20.0]])}
    assignments = jnp.asarray([0, 0, 1, 1])
    weights = jnp.asarray([1.0, 1.0, 1.0, 3.0])
    out = cluster_fedavg(stacked, assignments, weights, k=2)
    np.testing.assert_allclose(np.asarray(out["w"][:, 0]),
                               [2.0, 2.0, 17.5, 17.5])


def test_cluster_fedavg_identity_for_singleton_clusters():
    stacked = {"w": jax.random.normal(KEY, (3, 4))}
    out = cluster_fedavg(stacked, jnp.asarray([0, 1, 2]),
                         jnp.asarray([5.0, 1.0, 2.0]), k=3)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(stacked["w"]), rtol=1e-6)


def test_cluster_psum_fedavg_single_client_mesh():
    """Fleet-regime path on a 1-device 'pod' mesh: aggregation of a
    single client is the identity."""
    from jax.sharding import PartitionSpec as P
    from repro.core.aggregation import cluster_psum_fedavg
    mesh = jax.make_mesh((1,), ("pod",))
    params = {"w": jnp.asarray([[1.0, 2.0]])}

    def body(p, w, c):
        inner = jax.tree.map(lambda x: x[0], p)
        out = cluster_psum_fedavg(inner, w[0], c[0], 3, "pod")
        return jax.tree.map(lambda x: x[None], out)

    fn = jax.shard_map(body, mesh=mesh, check_vma=False,
                       in_specs=(P("pod"), P("pod"), P("pod")),
                       out_specs=P("pod"))
    out = fn(params, jnp.asarray([2.0]), jnp.asarray([1], jnp.int32))
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(params["w"]))


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs >=4 devices (run via ./test.sh)")
def test_cluster_fedavg_matches_psum_fedavg_shard_map():
    """Sim-regime segment-sum Eq.2 == fleet-regime masked-psum Eq.2 on a
    real multi-device 'pod' mesh."""
    from jax.sharding import PartitionSpec as P
    from repro.core.aggregation import cluster_psum_fedavg
    n, k = 4, 2
    mesh = jax.make_mesh((n,), ("pod",))
    stacked = {"w": jax.random.normal(KEY, (n, 3, 2)),
               "b": jax.random.normal(jax.random.PRNGKey(7), (n, 5))}
    assignments = jnp.asarray([0, 1, 0, 1], jnp.int32)
    weights = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    expect = cluster_fedavg(stacked, assignments, weights, k=k)

    def body(p, w, c):
        inner = jax.tree.map(lambda x: x[0], p)
        out = cluster_psum_fedavg(inner, w[0], c[0], k, "pod")
        return jax.tree.map(lambda x: x[None], out)

    fn = jax.shard_map(body, mesh=mesh, check_vma=False,
                       in_specs=(P("pod"), P("pod"), P("pod")),
                       out_specs=P("pod"))
    got = fn(stacked, weights, assignments)
    for key in ("w", "b"):
        np.testing.assert_allclose(np.asarray(got[key]),
                                   np.asarray(expect[key]),
                                   rtol=1e-5, atol=1e-6)
