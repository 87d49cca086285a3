"""Brain Storm Aggregation (paper §III.C).

The coordinator's per-round decision, mirroring the paper's server
whose *only* job is assigning neighbours:

  1. **Select cluster center** — the best validation score in each
     cluster.
  2. **Brain storm** — per cluster draw r1~U[0,1]; if r1 > p1 replace
     the center with a random member. Then per cluster draw r2; if
     r2 > p2 swap this cluster's center with another cluster's center
     (the swapped clients trade cluster membership for this round's
     aggregation — the "exchange individuals between clusters" move
     that fights non-IID local optima).
  3. **Parameter aggregation** — Eq. 2: sample-count-weighted FedAvg
     within each (post-swap) cluster; the jit-able segment-sum version
     lives in :mod:`repro.core.aggregation`.

Two implementations of the same decision procedure:

* :func:`brain_storm_jax` — the engine path (`repro.core.engine`):
  fixed-shape, `jax.random`-key-driven, fully traceable, so the whole
  BSO round (local steps + coordinator + Eq. 2) fuses into ONE jit'd
  device program and scans over rounds. Centers come from a masked
  per-cluster argmax, random members from a masked Gumbel-argmax, and
  the sequential cross-cluster swaps unroll over the static ``k``.
* :func:`brain_storm` — the original host-side numpy version, kept as
  the parity oracle (the two consume different RNG streams, so parity
  is statistical: same event *rates*, same structural invariants).

With the paper's p1=0.9 / p2=0.8 and r > p triggering, disruption rates
are 10% / 20% per cluster per round.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class BSAPlan:
    """The coordinator's per-round output."""
    assignments: np.ndarray            # (N,) effective cluster of each client
    centers: np.ndarray                # (K,) client index of each cluster center
    events: List[str] = field(default_factory=list)


def brain_storm(rng: np.random.Generator, assignments: np.ndarray,
                val_scores: np.ndarray, k: int, p1: float, p2: float) -> BSAPlan:
    """Pure host-side BSA planning. ``assignments`` come from k-means on
    the distribution summaries; ``val_scores`` are the clients' local
    validation accuracies (shared within the cluster, paper step 1)."""
    assignments = np.asarray(assignments).copy()
    val_scores = np.asarray(val_scores)
    events: List[str] = []

    # 1. centers = best validation score per cluster
    centers = np.full((k,), -1, dtype=np.int64)
    for c in range(k):
        members = np.where(assignments == c)[0]
        if len(members) == 0:
            continue
        centers[c] = members[np.argmax(val_scores[members])]

    # 2a. random center replacement (r1 > p1)
    for c in range(k):
        members = np.where(assignments == c)[0]
        if len(members) == 0:
            continue
        r1 = rng.uniform()
        if r1 > p1:
            new_center = int(rng.choice(members))
            if new_center != centers[c]:
                events.append(f"replace: cluster {c} center "
                              f"{centers[c]} -> {new_center} (r1={r1:.3f})")
            centers[c] = new_center

    # 2b. cross-cluster center swap (r2 > p2)
    occupied = [c for c in range(k) if centers[c] >= 0]
    for c in occupied:
        r2 = rng.uniform()
        if r2 > p2 and len(occupied) > 1:
            other = int(rng.choice([o for o in occupied if o != c]))
            ci, oi = centers[c], centers[other]
            centers[c], centers[other] = oi, ci
            # the swapped clients also trade aggregation membership
            assignments[ci], assignments[oi] = assignments[oi], assignments[ci]
            events.append(f"swap: centers of clusters {c} and {other} "
                          f"(clients {ci} <-> {oi}, r2={r2:.3f})")

    return BSAPlan(assignments=assignments, centers=centers, events=events)


def brain_storm_jax(key, assignments, val_scores, k: int, p1, p2):
    """Traceable BSA planning — the same decision procedure as
    :func:`brain_storm`, expressed in fixed shapes over a static ``k``.

    assignments: (N,) int cluster ids from k-means.
    val_scores:  (N,) float local validation accuracies.
    p1, p2:      python floats *or* traced scalars — they only enter
                 ``r > p`` comparisons, so the grid engine threads them
                 as per-row data through one compiled program.

    ``k`` is the static *pad*: per-cluster randomness derives from
    ``fold_in(key, c)`` (not a shape-``(k,)`` draw), so cluster c's
    draws are identical under any static ``k > c``. Clusters that are
    empty — including masked-off pad slots when k-means ran with
    ``k_active < k`` — are unoccupied and never replace, swap, or count,
    which makes a padded run bitwise-equal to a natively smaller-k run.

    Returns ``(assignments, centers, n_replaced, n_swapped)``:
    post-swap (N,) assignments, (k,) center client indices (-1 for an
    empty cluster), and the round's event counts (replacing the numpy
    version's event strings — the only host-facing residue).
    """
    with jax.named_scope("bso.brain_storm"):
        a = jnp.asarray(assignments, jnp.int32)
        val = jnp.asarray(val_scores, jnp.float32)
        member = a[None, :] == jnp.arange(k, dtype=jnp.int32)[:, None]   # (k, N)
        occupied = jnp.any(member, axis=1)                               # (k,)
        n_occ = jnp.sum(occupied.astype(jnp.int32))

        # 1. centers = best validation score per cluster (masked argmax)
        centers = jnp.argmax(jnp.where(member, val[None, :], -jnp.inf),
                             axis=1).astype(jnp.int32)
        centers = jnp.where(occupied, centers, -1)

        k_rep, k_member, k_swap, k_other = jax.random.split(key, 4)
        cluster_ids = jnp.arange(k, dtype=jnp.uint32)

        # 2a. random center replacement (r1 > p1): a uniformly random member
        # per cluster via masked Gumbel-argmax (one draw per (cluster,
        # client), no data-dependent shapes)
        r1 = jax.vmap(lambda c: jax.random.uniform(
            jax.random.fold_in(k_rep, c)))(cluster_ids)
        g = jax.vmap(lambda c: jax.random.gumbel(
            jax.random.fold_in(k_member, c), (a.shape[0],)))(cluster_ids)
        rand_member = jnp.argmax(jnp.where(member, g, -jnp.inf),
                                 axis=1).astype(jnp.int32)
        do_rep = (r1 > p1) & occupied
        n_replaced = jnp.sum((do_rep & (rand_member != centers)).astype(jnp.int32))
        centers = jnp.where(do_rep, rand_member, centers)

        # 2b. sequential cross-cluster center swaps (r2 > p2). Later swaps
        # must see earlier ones (same as the host loop), so unroll over the
        # static k; the swap partner is a uniformly random *other* occupied
        # cluster via masked Gumbel-argmax. The partner gumbels are drawn
        # per (c, other) pair so pad slots never perturb the real pairs.
        r2 = jax.vmap(lambda c: jax.random.uniform(
            jax.random.fold_in(k_swap, c)))(cluster_ids)
        g2 = jax.vmap(lambda c: jax.vmap(lambda o: jax.random.gumbel(
            jax.random.fold_in(jax.random.fold_in(k_other, c), o)))(
                cluster_ids))(cluster_ids)
        n_swapped = jnp.zeros((), jnp.int32)
        for c in range(k):
            valid_other = occupied & (jnp.arange(k) != c)
            other = jnp.argmax(jnp.where(valid_other, g2[c], -jnp.inf)
                               ).astype(jnp.int32)
            do_swap = (r2[c] > p2) & occupied[c] & (n_occ > 1)
            ci, oi = centers[c], centers[other]
            swapped_centers = centers.at[c].set(oi).at[other].set(ci)
            swapped_a = a.at[ci].set(a[oi]).at[oi].set(a[ci])
            centers = jnp.where(do_swap, swapped_centers, centers)
            a = jnp.where(do_swap, swapped_a, a)
            n_swapped = n_swapped + do_swap.astype(jnp.int32)

        return a, centers, n_replaced, n_swapped
