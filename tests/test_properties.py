"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.aggregation import (cluster_fedavg, cluster_fedavg_masked,
                                    cluster_fedavg_psum_masked, fedavg)
from repro.core.bso import brain_storm, brain_storm_jax
from repro.core.kmeans import kmeans
from repro.kernels import ops, ref

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")

floats = st.floats(-10, 10, allow_nan=False, allow_infinity=False, width=32)


# ------------------------------------------------------------- aggregation

@given(st.lists(floats, min_size=2, max_size=6),
       st.integers(1, 1000))
def test_fedavg_of_identical_params_is_identity(vals, w):
    """Aggregating N copies of the same model returns that model."""
    t = {"w": jnp.asarray(vals, jnp.float32)}
    out = fedavg([t, t, t], [w, 2 * w, 3 * w])
    np.testing.assert_allclose(np.asarray(out["w"]), vals, rtol=1e-5, atol=1e-5)


@given(st.integers(2, 10), st.integers(1, 4), st.integers(0, 2 ** 31 - 1))
def test_cluster_fedavg_is_convex_combination(n, k, seed):
    """Every aggregated leaf lies within [min, max] of cluster members."""
    rng = np.random.default_rng(seed)
    stacked = {"w": jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)}
    assignments = jnp.asarray(rng.integers(0, k, size=n))
    weights = jnp.asarray(rng.uniform(0.5, 5.0, size=n), jnp.float32)
    out = np.asarray(cluster_fedavg(stacked, assignments, weights, k=k)["w"])
    W = np.asarray(stacked["w"])
    a = np.asarray(assignments)
    for i in range(n):
        members = W[a == a[i]]
        assert out[i].min() >= members.min() - 1e-4
        assert out[i].max() <= members.max() + 1e-4


@given(st.integers(2, 8), st.integers(0, 2 ** 31 - 1))
def test_cluster_fedavg_idempotent(n, seed):
    """Aggregating twice equals aggregating once (fixed point)."""
    rng = np.random.default_rng(seed)
    stacked = {"w": jnp.asarray(rng.normal(size=(n, 4)), jnp.float32)}
    assignments = jnp.asarray(rng.integers(0, 2, size=n))
    weights = jnp.asarray(rng.uniform(1, 3, size=n), jnp.float32)
    once = cluster_fedavg(stacked, assignments, weights, k=2)
    twice = cluster_fedavg(once, assignments, weights, k=2)
    np.testing.assert_allclose(np.asarray(twice["w"]), np.asarray(once["w"]),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------- masked aggregation (churn Eq. 2)

def _masked_case(n, k, seed, drop_frac=0.0, zero_cluster=False):
    """Random churn-Eq.2 inputs: stacked params, assignments, base
    |D_h| weights, and a presence mask (optionally forcing cluster 0's
    effective weight to zero)."""
    rng = np.random.default_rng(seed)
    W = rng.normal(size=(n, 4)).astype(np.float32)
    a = rng.integers(0, k, size=n).astype(np.int32)
    base = rng.uniform(0.5, 5.0, size=n).astype(np.float32)
    present = rng.uniform(size=n) >= drop_frac
    if zero_cluster:
        present = present | True          # start all-present…
        present &= a != 0                 # …then hard-drop cluster 0
    if not present.any():
        present[0] = True
    weights = base * present.astype(np.float32)
    return W, a, base, weights, present


def _masked_oracle(W, a, weights, present, k):
    """Numpy reference for cluster_fedavg_masked: weighted per-cluster
    mean for present members of positively-weighted clusters, own
    params otherwise."""
    out = W.copy()
    tot = np.zeros(k, np.float64)
    sums = np.zeros((k,) + W.shape[1:], np.float64)
    for i in range(len(W)):
        tot[a[i]] += weights[i]
        sums[a[i]] += weights[i] * W[i]
    for i in range(len(W)):
        if present[i] and tot[a[i]] > 0.0:
            out[i] = (sums[a[i]] / tot[a[i]]).astype(np.float32)
    return out


@given(st.integers(3, 12), st.integers(1, 4), st.integers(0, 2 ** 31 - 1),
       st.floats(0.0, 0.8))
def test_cluster_fedavg_masked_matches_numpy_oracle(n, k, seed, drop):
    W, a, _, weights, present = _masked_case(n, k, seed, drop_frac=drop)
    out = cluster_fedavg_masked({"w": jnp.asarray(W)}, jnp.asarray(a),
                                jnp.asarray(weights), jnp.asarray(present),
                                k=k)["w"]
    np.testing.assert_allclose(np.asarray(out),
                               _masked_oracle(W, a, weights, present, k),
                               rtol=1e-5, atol=1e-5)


@given(st.integers(3, 12), st.integers(1, 4), st.integers(0, 2 ** 31 - 1),
       st.floats(0.0, 0.8))
def test_cluster_fedavg_masked_permutation_invariant(n, k, seed, drop):
    """Relabeling clients (permuting all per-client arrays together)
    permutes the output identically — no client is privileged."""
    W, a, _, weights, present = _masked_case(n, k, seed, drop_frac=drop)
    out = np.asarray(cluster_fedavg_masked(
        {"w": jnp.asarray(W)}, jnp.asarray(a), jnp.asarray(weights),
        jnp.asarray(present), k=k)["w"])
    perm = np.random.default_rng(seed ^ 0x5EED).permutation(n)
    out_p = np.asarray(cluster_fedavg_masked(
        {"w": jnp.asarray(W[perm])}, jnp.asarray(a[perm]),
        jnp.asarray(weights[perm]), jnp.asarray(present[perm]), k=k)["w"])
    np.testing.assert_allclose(out_p, out[perm], rtol=1e-5, atol=1e-6)


@given(st.integers(3, 12), st.integers(2, 4), st.integers(0, 2 ** 31 - 1))
def test_cluster_fedavg_masked_zero_weight_cluster_keeps_own(n, k, seed):
    """A cluster whose every member is hard-dropped aggregates nothing:
    its members keep their own params BITWISE (the zero-denominator
    guard), and no NaN ever surfaces."""
    W, a, _, weights, present = _masked_case(n, k, seed, zero_cluster=True)
    out = np.asarray(cluster_fedavg_masked(
        {"w": jnp.asarray(W)}, jnp.asarray(a), jnp.asarray(weights),
        jnp.asarray(present), k=k)["w"])
    assert not np.isnan(out).any()
    for i in range(n):
        if a[i] == 0 or not present[i]:
            assert np.array_equal(out[i], W[i])


@given(st.integers(3, 12), st.integers(1, 4), st.integers(0, 2 ** 31 - 1),
       st.floats(0.0, 0.8))
def test_cluster_fedavg_masked_stale_decay_zero_is_hard_mask(n, k, seed,
                                                            drop):
    """stale_decay = 0 semantics: base * 0.0**staleness (staleness > 0
    iff absent; numpy 0**0 == 1) is EXACTLY the hard mask
    base * present — the two churn options coincide at λ = 0."""
    W, a, base, _, present = _masked_case(n, k, seed, drop_frac=drop)
    staleness = (~present).astype(np.float32) * \
        np.random.default_rng(seed ^ 0xDECA).integers(
            1, 5, size=n).astype(np.float32)
    w_decay = base * np.float_power(0.0, staleness).astype(np.float32)
    w_hard = base * present.astype(np.float32)
    np.testing.assert_array_equal(w_decay, w_hard)
    out_d = cluster_fedavg_masked({"w": jnp.asarray(W)}, jnp.asarray(a),
                                  jnp.asarray(w_decay),
                                  jnp.asarray(present), k=k)["w"]
    out_h = cluster_fedavg_masked({"w": jnp.asarray(W)}, jnp.asarray(a),
                                  jnp.asarray(w_hard),
                                  jnp.asarray(present), k=k)["w"]
    assert np.array_equal(np.asarray(out_d), np.asarray(out_h))


@given(st.integers(3, 12), st.integers(1, 4), st.integers(0, 2 ** 31 - 1),
       st.floats(0.0, 0.8))
def test_cluster_fedavg_masked_mean_is_bounded_by_members(n, k, seed, drop):
    """Every receiving client's aggregate lies inside the [min, max]
    envelope of its cluster's positively-weighted members (weighted
    mean is a convex combination)."""
    W, a, _, weights, present = _masked_case(n, k, seed, drop_frac=drop)
    out = np.asarray(cluster_fedavg_masked(
        {"w": jnp.asarray(W)}, jnp.asarray(a), jnp.asarray(weights),
        jnp.asarray(present), k=k)["w"])
    tot = np.bincount(a, weights=weights, minlength=k)
    for i in range(n):
        if not (present[i] and tot[a[i]] > 0.0):
            continue
        members = W[(a == a[i]) & (weights > 0.0)]
        assert (out[i] >= members.min(axis=0) - 1e-4).all()
        assert (out[i] <= members.max(axis=0) + 1e-4).all()


@given(st.integers(3, 10), st.integers(1, 4), st.integers(0, 2 ** 31 - 1),
       st.floats(0.0, 0.8))
@settings(max_examples=10, deadline=None)
def test_cluster_fedavg_psum_masked_matches_segment_sum(n, k, seed, drop):
    """Fleet-regime masked psum Eq. 2 == sim-regime masked segment-sum
    on a 1-device 'pod' mesh (whole swarm in one shard; the psum is the
    identity reduction, so any divergence is in the shared math)."""
    from jax.sharding import PartitionSpec as P
    W, a, _, weights, present = _masked_case(n, k, seed, drop_frac=drop)
    expect = cluster_fedavg_masked({"w": jnp.asarray(W)}, jnp.asarray(a),
                                   jnp.asarray(weights),
                                   jnp.asarray(present), k=k)["w"]
    mesh = jax.make_mesh((1,), ("pod",))

    def body(p, c, w, m):
        inner = jax.tree.map(lambda x: x[0], p)
        out = cluster_fedavg_psum_masked(inner, c[0], w[0], m[0], k, "pod")
        return jax.tree.map(lambda x: x[None], out)

    fn = jax.shard_map(body, mesh=mesh, check_vma=False,
                       in_specs=(P("pod"), P("pod"), P("pod"), P("pod")),
                       out_specs=P("pod"))
    got = fn({"w": jnp.asarray(W)[None]}, jnp.asarray(a)[None],
             jnp.asarray(weights)[None], jnp.asarray(present)[None])["w"][0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ kmeans

@given(st.integers(4, 30), st.integers(2, 5), st.integers(0, 2 ** 31 - 1))
def test_kmeans_assignment_is_locally_optimal(n, k, seed):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(n, 5)), jnp.float32)
    C, a = kmeans(jax.random.PRNGKey(seed % 1000), X, k, iters=15)
    d = np.asarray(jnp.sum((X[:, None] - C[None]) ** 2, axis=-1))
    a = np.asarray(a)
    for i in range(n):
        assert d[i, a[i]] <= d[i].min() + 1e-4


# -------------------------------------------------------------- brain storm

@given(st.integers(0, 10_000),
       st.floats(0, 1), st.floats(0, 1),
       st.integers(6, 20), st.integers(2, 4))
def test_brain_storm_invariants(seed, p1, p2, n, k):
    """For any (p1, p2): centers are valid members of their (post-swap)
    clusters; assignments remain a partition of the same client set."""
    rng = np.random.default_rng(seed)
    val = rng.uniform(size=n).astype(np.float32)
    assignments = rng.integers(0, k, size=n)
    plan = brain_storm(rng, assignments.copy(), val, k, p1, p2)
    assert sorted(plan.assignments.tolist()) != [] \
        and len(plan.assignments) == n
    # same multiset of cluster labels (swaps exchange, never create/destroy)
    assert sorted(plan.assignments.tolist()) == sorted(assignments.tolist())
    for c in range(k):
        if plan.centers[c] >= 0:
            assert plan.assignments[plan.centers[c]] == c


def _bsa_case(seed, n, k):
    rng = np.random.default_rng(seed)
    return (rng, rng.integers(0, k, size=n).astype(np.int32),
            rng.uniform(size=n).astype(np.float32))


@given(st.integers(0, 2 ** 31 - 1), st.integers(5, 24), st.integers(2, 5),
       st.floats(0, 1), st.floats(0, 1))
def test_brain_storm_jax_oracle_shared_invariants(seed, n, k, p1, p2):
    """For any (p1, p2, k, key): both implementations preserve the
    cluster-membership multiset, keep every center a member of its
    post-swap cluster, and bound event counts by the occupied-cluster
    count (each cluster initiates at most one replace and one swap)."""
    rng, a0, val = _bsa_case(seed, n, k)
    n_occ = len(np.unique(a0))

    a, c, n_rep, n_swap = brain_storm_jax(jax.random.PRNGKey(seed),
                                          a0, val, k, p1, p2)
    a, c = np.asarray(a), np.asarray(c)
    assert sorted(a.tolist()) == sorted(a0.tolist())
    for cl in range(k):
        if c[cl] >= 0:
            assert a[c[cl]] == cl
    assert 0 <= int(n_rep) <= n_occ
    assert 0 <= int(n_swap) <= n_occ

    plan = brain_storm(rng, a0.copy(), val, k, p1, p2)
    assert sorted(plan.assignments.tolist()) == sorted(a0.tolist())
    for cl in range(k):
        if plan.centers[cl] >= 0:
            assert plan.assignments[plan.centers[cl]] == cl
    assert sum("replace" in e for e in plan.events) <= n_occ
    assert sum("swap" in e for e in plan.events) <= n_occ


@given(st.integers(0, 2 ** 31 - 1), st.integers(5, 24), st.integers(2, 5))
def test_brain_storm_p_one_edge_is_deterministic_noop(seed, n, k):
    """p1 = p2 = 1: r > p never fires, so BOTH implementations are
    deterministic and must agree exactly — assignments untouched,
    centers = per-cluster best-validation member, zero events."""
    rng, a0, val = _bsa_case(seed, n, k)
    a, c, n_rep, n_swap = brain_storm_jax(jax.random.PRNGKey(seed),
                                          a0, val, k, 1.0, 1.0)
    plan = brain_storm(rng, a0.copy(), val, k, 1.0, 1.0)
    np.testing.assert_array_equal(np.asarray(a), a0)
    np.testing.assert_array_equal(plan.assignments, a0)
    np.testing.assert_array_equal(np.asarray(c), plan.centers)
    assert int(n_rep) == 0 and int(n_swap) == 0 and plan.events == []


@given(st.integers(0, 2 ** 31 - 1), st.integers(5, 24), st.integers(2, 5))
def test_brain_storm_p_zero_edge_always_fires(seed, n, k):
    """p1 = p2 = 0: every occupied cluster replaces its center with a
    random member and initiates a swap (when >= 2 clusters are
    occupied) — in both implementations. The invariants must survive
    maximum disruption."""
    rng, a0, val = _bsa_case(seed, n, k)
    n_occ = len(np.unique(a0))

    a, c, n_rep, n_swap = brain_storm_jax(jax.random.PRNGKey(seed),
                                          a0, val, k, 0.0, 0.0)
    a, c = np.asarray(a), np.asarray(c)
    assert sorted(a.tolist()) == sorted(a0.tolist())
    for cl in range(k):
        if c[cl] >= 0:
            assert a[c[cl]] == cl
    assert int(n_swap) == (n_occ if n_occ > 1 else 0)

    plan = brain_storm(rng, a0.copy(), val, k, 0.0, 0.0)
    n_swaps_np = sum("swap" in e for e in plan.events)
    assert n_swaps_np == (n_occ if n_occ > 1 else 0)
    for cl in range(k):
        if plan.centers[cl] >= 0:
            assert plan.assignments[plan.centers[cl]] == cl


# ------------------------------------------------------------------ kernels

@given(st.integers(1, 3), st.integers(1, 3), st.integers(0, 2 ** 31 - 1))
def test_param_stats_matches_numpy(r, c, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(r * 37, c * 11)) * 3, jnp.float32)
    m, v = ops.param_stats(x)
    np.testing.assert_allclose(float(m), float(np.mean(np.asarray(x))),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(v), float(np.var(np.asarray(x))),
                               rtol=1e-3, atol=1e-3)


@given(st.integers(1, 40), st.integers(1, 6), st.integers(2, 5),
       st.integers(0, 2 ** 31 - 1))
def test_kmeans_assign_kernel_matches_ref(n, f, k, seed):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(n, f)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(k, f)), jnp.float32)
    out = ops.kmeans_assign(X, C)
    expect = ref.ref_kmeans_assign(X, C)
    assert np.array_equal(np.asarray(out), np.asarray(expect))


# ----------------------------------------------------------------- softmax

@given(st.integers(1, 2), st.integers(1, 4), st.integers(0, 2 ** 31 - 1))
def test_flash_attention_rowsum_property(b, h, seed):
    """Attention output of constant-v inputs equals that constant
    (softmax rows sum to 1)."""
    rng = np.random.default_rng(seed)
    S, D = 128, 64
    q = jnp.asarray(rng.normal(size=(b, h, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, S, D)), jnp.float32)
    v = jnp.ones((b, h, S, D), jnp.float32) * 0.5
    out = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), 0.5, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- causality

@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_attention_is_causal(seed):
    """Perturbing a future token must not change past logits."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("granite-3-2b").smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    S = 12
    toks = rng.integers(0, cfg.vocab_size, size=(1, S)).astype(np.int32)
    t = int(rng.integers(1, S))
    toks2 = toks.copy()
    toks2[0, t] = (toks2[0, t] + 1) % cfg.vocab_size
    a, _ = model.forward(params, {"tokens": jnp.asarray(toks)})
    b, _ = model.forward(params, {"tokens": jnp.asarray(toks2)})
    np.testing.assert_allclose(np.asarray(a[:, :t]), np.asarray(b[:, :t]),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(a[:, t:] - b[:, t:]))) > 1e-6


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=8, deadline=None)
def test_ssd_is_causal_and_state_consistent(seed):
    """Mamba2 SSD: (a) causality; (b) splitting a sequence in half and
    passing the final state must equal processing it whole."""
    import dataclasses
    from repro.configs import get_config
    from repro.models.ssm import apply_ssm, init_ssm
    cfg = dataclasses.replace(get_config("mamba2-370m").smoke(), ssm_chunk=8)
    p = init_ssm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    S = 32
    x = jnp.asarray(rng.normal(size=(1, S, cfg.d_model)) * 0.1, jnp.float32)
    y_full, state_full = apply_ssm(p, x, cfg)
    # causality
    x2 = x.at[0, S - 4].add(1.0)
    y2, _ = apply_ssm(p, x2, cfg)
    np.testing.assert_allclose(np.asarray(y2[:, :S - 4]),
                               np.asarray(y_full[:, :S - 4]),
                               rtol=1e-4, atol=1e-5)
    # carry passing (SSD state + conv boundary frames) across a split
    y_a, (st_a, conv_a) = apply_ssm(p, x[:, :S // 2], cfg, return_carry=True)
    y_b, st_b = apply_ssm(p, x[:, S // 2:], cfg, initial_state=st_a,
                          initial_conv=conv_a)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y_a, y_b], 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_b), np.asarray(state_full),
                               rtol=1e-4, atol=1e-5)
