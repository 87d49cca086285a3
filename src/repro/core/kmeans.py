"""k-means over client distribution summaries (paper §III.B).

Lloyd iterations with k-means++ seeding, fully jit-able
(lax.fori_loop + static k). Empty clusters are re-seeded to *distinct*
far points — the j-th empty cluster takes the j-th farthest point from
its assigned centroid — so k clusters survive even with N=14 clients
and re-seeded centroids can actually separate (a single shared far
point would leave duplicate centroids forever).

**Masked static-max clusters** (the grid engine's k axis): every entry
point takes an optional traced ``k_active`` — the static ``k`` becomes
an upper bound (pad), and only clusters ``< k_active`` can be seeded,
assigned to, or re-seeded. Per-index randomness derives from
``fold_in(key, i)`` rather than a shape-``(k,)`` draw, so the first
``k_active`` draws are *bitwise identical* no matter the static pad:
a ``k=k_max, k_active=j`` run reproduces a native ``k=j`` run exactly
(``tests/test_grid.py`` pins this), which is what lets
``engine.run_grid`` vmap a cluster-count ablation into one program.
``k_active=None`` keeps the plain static-k path.

**Masked points** (the churn engine's participation axis): every entry
point also takes an optional traced ``mask`` over the N points — absent
clients keep receiving assignments (cluster membership feeds the
staleness-weighted Eq. 2) but contribute nothing to seeding or centroid
means, and a cluster whose members are all absent is treated as empty
and rides the far-point reseed (present candidates only). An all-ones
mask is bitwise the unmasked run.

**Weighted points** (the hierarchical engine's summary axis): every
entry point also takes an optional traced ``weights`` over the N points
— the input rows may themselves be *centroids from a lower tier*
carrying member counts, so seeding probabilities scale to ``d * w``,
centroid means become weight-weighted means, and zero-weight rows are
excluded from seeding and reseeds exactly like masked-out points (a
pod-cluster that captured no clients must not anchor a global
centroid). ``weights=None`` is bitwise the unweighted run; ``weights``
composes multiplicatively with ``mask``.

The distance/assign step has two interchangeable implementations:
the jnp path below (the oracle) and the ``kmeans_assign`` Pallas kernel
(``use_pallas=True``) — one distance-matmul+argmin device program per
Lloyd iteration. The masked path always assigns through the jnp
implementation (the kernel has no mask operand); since ``k`` is tiny
the matmul is negligible either way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _pairwise_sq_dists(X, C):
    """(N, K) squared euclidean distances."""
    x2 = jnp.sum(X * X, axis=1, keepdims=True)
    c2 = jnp.sum(C * C, axis=1)[None, :]
    return jnp.maximum(x2 + c2 - 2.0 * X @ C.T, 0.0)


def _point_weights(X, mask, weights):
    """Combine the participation mask and per-point weights into
    (wf, pos): a float scale for distances/means (or None when both
    inputs are None — the bitwise-unchanged fast path) and a bool
    eligibility mask for seeding/reseed targets (or None likewise).
    ``weights``-only and ``mask``-only paths each reproduce the
    respective single-axis behaviour; together they compose
    multiplicatively (an absent point keeps zero weight)."""
    if weights is None and mask is None:
        return None, None
    if weights is None:
        m = jnp.asarray(mask, bool)
        return m.astype(X.dtype), m
    w = jnp.asarray(weights, X.dtype)
    if mask is not None:
        w = w * jnp.asarray(mask, X.dtype)
    return w, w > 0


def kmeans_pp_init(key, X, k: int, mask=None, weights=None):
    """k-means++ seeding. Draws derive per-index from ``fold_in`` so
    seeds 0..j are identical for every static ``k >= j`` — the masked
    path's pad-invariance. Deliberately unmasked over *clusters*: pad
    slots beyond a caller's ``k_active`` still seed (fixed shapes,
    identical first ``k_active`` draws) and are masked out of every
    downstream assignment instead.

    ``mask`` (a traced (N,) participation mask, or None) excludes
    absent *points* from seeding: the first seed's uniform draw is
    remapped onto the present subsequence and the ++ probabilities of
    absent points are zeroed. With ``mask`` all-ones both moves are
    bitwise identities (the remap fixes the same index, ``d * 1.0`` is
    exact), so a fully-present masked run reproduces the unmasked run
    exactly — the churn engine's parity anchor.

    ``weights`` (a traced (N,) non-negative weight vector, or None)
    makes the seeding *weighted*: the first seed is uniform over
    positive-weight points and the ++ probabilities scale to
    ``d * w`` — the classic weighted-k-means++ rule, which is what lets
    the rows of ``X`` be lower-tier centroids carrying member counts.
    ``weights=None`` is bitwise the unweighted path."""
    N = X.shape[0]
    r0 = jax.random.randint(jax.random.fold_in(key, 0), (), 0, N)
    wf, pos = _point_weights(X, mask, weights)
    if pos is None:
        idx0 = r0
    else:
        # uniform over the eligible subsequence: r0 mod n_eligible
        # ranks into the cumulative-eligibility prefix (identity when
        # all eligible: cumsum hits r0+1 first at index r0)
        cum = jnp.cumsum(pos.astype(jnp.int32))
        rank = r0 % jnp.maximum(cum[-1], 1)
        idx0 = jnp.clip(jnp.searchsorted(cum, rank + 1), 0, N - 1)
    C = jnp.zeros((k, X.shape[1]), X.dtype).at[0].set(X[idx0])

    def body(i, C):
        # distances against the first i chosen centroids only
        valid = jnp.arange(k) < i
        dists = _pairwise_sq_dists(X, C)
        dists = jnp.where(valid[None, :], dists, jnp.inf)
        d = jnp.min(dists, axis=1)
        if wf is not None:
            d = d * wf
        p = d / jnp.maximum(d.sum(), 1e-12)
        nxt = jax.random.choice(jax.random.fold_in(key, i), N, p=p)
        return C.at[i].set(X[nxt])

    return jax.lax.fori_loop(1, k, body, C)


def assign(X, C, k_active=None):
    """Nearest-centroid assignment (the kmeans_assign kernel's math).
    With ``k_active`` only clusters ``< k_active`` are eligible."""
    d = _pairwise_sq_dists(X, C)
    if k_active is not None:
        d = jnp.where(jnp.arange(C.shape[0])[None, :] < k_active,
                      d, jnp.inf)
    return jnp.argmin(d, axis=1)


def _assign_fn(use_pallas: bool, k_active=None):
    if k_active is not None:
        # masked path: the Pallas kernel has no mask operand; the jnp
        # argmin over masked distances is the one implementation
        return lambda X, C: assign(X, C, k_active)
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.kmeans_assign
    return assign


def lloyd_step(X, C, k: int, *, use_pallas: bool = False, k_active=None,
               mask=None, weights=None):
    """One Lloyd iteration: assign, recompute means, reseed empties.
    Only clusters ``< k_active`` count as re-seedable empties — the
    inactive pad slots must stay out of the far-point budget or a
    ``k_active=j`` run would burn its farthest points on dead slots.

    ``mask`` (a traced (N,) participation mask, or None) is the churn
    engine's point axis: absent points are still *assigned* (their
    cluster membership feeds the staleness-weighted Eq. 2) but carry
    zero weight in the centroid means, and a cluster whose members are
    all absent counts as EMPTY — it rides the existing far-point reseed
    (restricted to present candidates), which is exactly the
    all-absent-cluster fallback the churn round relies on. All-ones
    mask is bitwise the unmasked step (``onehot * 1.0`` and
    ``where(True, d, -inf)`` are identities).

    ``weights`` (a traced (N,) non-negative weight vector, or None)
    turns the means into weighted means — ``counts`` become weight
    sums, so a row of ``X`` can stand for a whole pod-cluster of
    clients. Zero-weight rows behave like masked-out points (no vote
    in the means, never a reseed target, and a cluster holding only
    zero-weight rows counts as empty). ``weights=None`` keeps the
    unweighted denominator floor of 1.0 bitwise; with weights the
    floor drops to 1e-9 so fractional weight sums still produce true
    weighted means (empty rows get reseeded regardless)."""
    a = _assign_fn(use_pallas, k_active)(X, C)
    wf, pos = _point_weights(X, mask, weights)
    onehot = jax.nn.one_hot(a, k, dtype=X.dtype)             # (N, K)
    if wf is not None:
        onehot = onehot * wf[:, None]
    counts = onehot.sum(axis=0)                              # (K,)
    sums = onehot.T @ X                                      # (K, F)
    floor = 1.0 if weights is None else 1e-9
    newC = sums / jnp.maximum(counts[:, None], floor)
    # empty clusters -> distinct far points: rank points by distance to
    # their current centroid (farthest first) and hand the j-th empty
    # cluster the j-th farthest point. Distance to the *assigned*
    # centroid equals the min pairwise distance, so reuse `a` instead
    # of a second full (N, K) distance matmul (the Pallas assign call
    # is opaque to XLA's CSE).
    diff = X - C[a]
    d = jnp.sum(diff * diff, axis=1)
    if pos is not None:
        # absent / zero-weight points can never be reseed targets
        d = jnp.where(pos, d, -jnp.inf)
    far_order = jnp.argsort(-d)                              # (N,)
    empty = counts == 0
    if k_active is not None:
        empty = empty & (jnp.arange(k) < k_active)
    rank = jnp.clip(jnp.cumsum(empty.astype(jnp.int32)) - 1,
                    0, X.shape[0] - 1)                       # (K,)
    newC = jnp.where(empty[:, None], X[far_order[rank]], newC)
    return newC


def kmeans(key, X, k: int, iters: int = 20, *, use_pallas: bool = False,
           k_active=None, mask=None, weights=None):
    """Returns (centroids (k,F), assignments (N,)).

    ``k`` is static (shapes); ``k_active`` optionally restricts the
    run to the first ``k_active`` clusters as traced data — assignments
    land in ``[0, k_active)`` and match a native ``k=k_active`` run
    bitwise (centroid rows ``>= k_active`` are dead pad).

    ``mask`` (a traced (N,) participation mask, or None) excludes
    absent points from seeding, centroid means and reseeds while still
    assigning every point a cluster (see :func:`lloyd_step`); all-ones
    is bitwise the unmasked run.

    ``weights`` (a traced (N,) non-negative weight vector, or None)
    runs *weighted* k-means: ++ seeding draws scale to ``d * w`` and
    Lloyd means weight each row — the centroid-input mode, where the
    rows of ``X`` are themselves centroids from a lower tier and
    ``weights`` their member counts (the hierarchical coordinator's
    global tier). ``weights=None`` is bitwise the unweighted run;
    composes multiplicatively with ``mask``."""
    with jax.named_scope("bso.kmeans"):
        C0 = kmeans_pp_init(key, X, k, mask=mask, weights=weights)
        C = jax.lax.fori_loop(
            0, iters,
            lambda it, C: lloyd_step(X, C, k, use_pallas=use_pallas,
                                     k_active=k_active, mask=mask,
                                     weights=weights), C0)
        return C, _assign_fn(use_pallas, k_active)(X, C)
