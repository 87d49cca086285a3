"""Parameter aggregation (paper Eq. 2) — the client-to-client step.

Sim regime: clients live on one host as a stacked pytree; cluster
FedAvg is a segment-sum over the client axis (jit-able, O(N) with no
server bottleneck).

Fleet regime: the identical math expressed as a *masked weighted psum*
over the ``clients`` mesh axis inside shard_map — cluster-restricted
all-reduce, i.e. swarm learning's peer-to-peer exchange as a TPU
collective (see repro/launch/swarm_fleet.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.utils.tree import tree_weighted_sum


def fedavg(params_list, n_samples):
    """Classic FedAvg over an explicit list of client pytrees."""
    w = jnp.asarray(n_samples, jnp.float32)
    w = w / jnp.maximum(w.sum(), 1e-9)
    return tree_weighted_sum(params_list, w)


def singleton_assignments(n: int):
    """Assignments placing every client in its own cluster, which makes
    :func:`cluster_fedavg` (with ``k >= n``) the *bitwise* identity:
    each singleton's weight normalises to exactly ``w / w == 1.0`` and
    its segment sum is a single float32 copy. This is how the sweep
    engine expresses the paper's local-only baseline as the same
    aggregation program as the other methods."""
    return jnp.arange(n, dtype=jnp.int32)


def cluster_fedavg(stacked_params, assignments, n_samples, k: int):
    """Eq. 2 within every cluster simultaneously.

    stacked_params: pytree with leading client axis N.
    assignments:    (N,) int cluster ids (post brain-storm).
    n_samples:      (N,) training set sizes |D_h|.
    ``k`` only needs to upper-bound the labels in ``assignments``;
    passing ``k = N`` with labels drawn from a smaller range computes
    the same sums *bitwise* — per-segment partial sums and the gather
    back are unchanged by trailing empty segments. The sweep AND grid
    engines rely on exactly this: one ``k = N`` segment layout serves
    every Table-II method row and every masked-k grid row (whose
    k-means labels live in ``[0, point.n_clusters)`` under the static
    pad ``k_max``), so the aggregation plan never needs a traced
    segment count.
    Returns the stacked pytree where client i holds its cluster's
    aggregated model (the redistribution step).
    """
    with jax.named_scope("bso.eq2"):
        assignments = jnp.asarray(assignments)
        w = jnp.asarray(n_samples, jnp.float32)
        # per-cluster weight normalisation: |D_h| / |D_{G_k}|
        cluster_tot = jax.ops.segment_sum(w, assignments, num_segments=k)
        wn = w / jnp.maximum(cluster_tot[assignments], 1e-9)

        def agg_leaf(leaf):
            lf = leaf.astype(jnp.float32)
            weighted = lf * wn.reshape((-1,) + (1,) * (lf.ndim - 1))
            sums = jax.ops.segment_sum(weighted, assignments, num_segments=k)
            return sums[assignments].astype(leaf.dtype)

        return jax.tree.map(agg_leaf, stacked_params)


def cluster_fedavg_masked(stacked_params, assignments, weights, present,
                          k: int):
    """Churn-aware Eq. 2: participation-masked cluster FedAvg.

    The same op sequence as :func:`cluster_fedavg` — per-cluster weight
    normalisation, weighted segment-sum, gather back — with two churn
    semantics on top:

    * ``weights`` are the *effective* Eq. 2 weights, not raw |D_h|:
      the caller has already folded participation in (0 for a
      hard-masked absent client, |D_h|·λ^staleness for the
      staleness-weighted option), so an absent client contributes
      nothing (or a decayed echo) to its cluster's aggregate.
    * ``present`` gates who RECEIVES: absent clients keep their own
      (stale) params instead of taking the cluster aggregate — they
      were not part of this round's exchange.

    A cluster whose total effective weight is zero (every member absent
    under hard masking) produces no aggregate; any client reading from
    it falls back to its own params — the explicit guard that keeps the
    zero denominator from ever surfacing as NaNs. (K-means handles the
    same situation upstream via its empty-cluster reseed when the stats
    matrix is masked; this guard covers assignments arriving from
    *outside* k-means, e.g. a stale coordinator decision.)

    With ``present`` all-ones and ``weights = n_samples * 1.0`` this is
    BITWISE :func:`cluster_fedavg`: multiplying a float by 1.0 is
    exact, ``where(True, agg, own)`` is the identity, and positive
    |D_h| keep every cluster total strictly positive —
    ``tests/test_churn.py`` pins the equivalence.
    """
    with jax.named_scope("bso.eq2"):
        assignments = jnp.asarray(assignments)
        w = jnp.asarray(weights, jnp.float32)
        present = jnp.asarray(present, bool)
        cluster_tot = jax.ops.segment_sum(w, assignments, num_segments=k)
        wn = w / jnp.maximum(cluster_tot[assignments], 1e-9)
        # receive = participated AND the cluster actually aggregated
        take = present & (cluster_tot[assignments] > 0.0)

        def agg_leaf(leaf):
            lf = leaf.astype(jnp.float32)
            weighted = lf * wn.reshape((-1,) + (1,) * (lf.ndim - 1))
            sums = jax.ops.segment_sum(weighted, assignments, num_segments=k)
            agg = sums[assignments].astype(leaf.dtype)
            m = take.reshape((-1,) + (1,) * (leaf.ndim - 1))
            return jnp.where(m, agg, leaf)

        return jax.tree.map(agg_leaf, stacked_params)


def cluster_fedavg_psum(stacked_params, assignments, n_samples, k: int,
                        axis_name: str):
    """Eq. 2 for a *local slice* of the client axis inside shard_map —
    the fleet driver's aggregation.

    Same math as :func:`cluster_fedavg`, with the client axis split
    over the ``axis_name`` mesh axis (the fleet's ``pod`` axis): each
    shard segment-sums its local clients into the global ``k`` cluster
    slots, one psum per pytree (the swarm's client-to-client exchange
    as a collective), then every client reads back its cluster's sum.
    ``assignments`` / ``n_samples`` are the local (n_local,) slices
    carrying *global* cluster ids. With one client per pod this is
    :func:`cluster_psum_fedavg`'s math on a batched layout; with the
    whole swarm in one shard it reduces to :func:`cluster_fedavg`.
    """
    assignments = jnp.asarray(assignments)
    w = jnp.asarray(n_samples, jnp.float32)
    cluster_tot = jax.lax.psum(
        jax.ops.segment_sum(w, assignments, num_segments=k), axis_name)
    wn = w / jnp.maximum(cluster_tot[assignments], 1e-9)

    def agg_leaf(leaf):
        lf = leaf.astype(jnp.float32)
        weighted = lf * wn.reshape((-1,) + (1,) * (lf.ndim - 1))
        sums = jax.lax.psum(
            jax.ops.segment_sum(weighted, assignments, num_segments=k),
            axis_name)
        return sums[assignments].astype(leaf.dtype)

    return jax.tree.map(agg_leaf, stacked_params)


def cluster_fedavg_psum_masked(stacked_params, assignments, weights,
                               present, k: int, axis_name: str):
    """:func:`cluster_fedavg_masked` for a *local slice* of the client
    axis inside shard_map — the fleet driver's churn-regime aggregation.
    ``assignments`` / ``weights`` / ``present`` are local slices with
    global cluster ids; the segment sums ride one psum each, and the
    zero-weight-cluster guard plus the present-only receive mask apply
    shard-locally (every shard sees the same psum'd cluster totals)."""
    assignments = jnp.asarray(assignments)
    w = jnp.asarray(weights, jnp.float32)
    present = jnp.asarray(present, bool)
    cluster_tot = jax.lax.psum(
        jax.ops.segment_sum(w, assignments, num_segments=k), axis_name)
    wn = w / jnp.maximum(cluster_tot[assignments], 1e-9)
    take = present & (cluster_tot[assignments] > 0.0)

    def agg_leaf(leaf):
        lf = leaf.astype(jnp.float32)
        weighted = lf * wn.reshape((-1,) + (1,) * (lf.ndim - 1))
        sums = jax.lax.psum(
            jax.ops.segment_sum(weighted, assignments, num_segments=k),
            axis_name)
        agg = sums[assignments].astype(leaf.dtype)
        m = take.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return jnp.where(m, agg, leaf)

    return jax.tree.map(agg_leaf, stacked_params)


def cluster_psum_fedavg(params, weight, my_cluster, k: int, axis_name: str):
    """Fleet-regime Eq. 2: inside shard_map over the client axis.

    params: this client's pytree; weight: scalar |D_h|;
    my_cluster: () int32 — this client's (post brain-storm) cluster id.

    One masked psum per cluster (k is small — 3 in the paper): every
    client contributes its weighted params only to its own cluster's
    sum, then reads back the sum for its cluster. Pure client-to-client
    collectives — no server, and a psum is exactly the "exchange
    parameters with peers" traffic of swarm learning on ICI/DCN.
    """
    my_w = weight.astype(jnp.float32)

    def one_cluster(c):
        sel = (my_cluster == c).astype(jnp.float32)
        num = jax.tree.map(
            lambda x: jax.lax.psum(x.astype(jnp.float32) * (my_w * sel), axis_name),
            params)
        den = jax.lax.psum(my_w * sel, axis_name)
        return num, den

    nums, dens = [], []
    for c in range(k):
        n, d = one_cluster(c)
        nums.append(n)
        dens.append(d)

    dens = jnp.stack(dens)                                # (k,)
    my_den = jnp.maximum(dens[my_cluster], 1e-9)

    def pick(x, *cluster_leaves):
        stacked = jnp.stack(cluster_leaves)               # (k, ...)
        return (stacked[my_cluster] / my_den).astype(x.dtype)

    return jax.tree.map(pick, params, *nums)
