"""Parameter-distribution summaries (paper §III.B).

Each client uploads only the *distribution* of its model parameters —
per-tensor (mean, variance, size) under the paper's Gaussian assumption —
never the parameters themselves. The resulting feature vector has
O(#tensors) dimensions (hundreds) instead of O(#params) (millions to
10^12), which is both the privacy and the communication-efficiency
argument of BSO-SL.

Note (DESIGN.md §8): the paper says "mean and covariance"; a full
covariance is O(n^2) and contradicts the paper's own communication
claim, so this is the diagonal (per-tensor variance) reading.

The reduction itself is a memory-bound pass over every parameter — on
TPU it is served by the ``param_stats`` / ``param_stats_batched``
Pallas kernels (``repro/kernels/param_stats.py``); the jnp paths below
are the oracles and the CPU/lowering path. The coordinator consumes the
whole swarm at once via ``swarm_distribution_matrix`` — one jit'd pass
over the client-stacked pytree, not a per-client host loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.utils.tree import tree_paths_and_leaves


def tensor_stats(x: jnp.ndarray):
    """(mean, var) of one tensor in fp32."""
    xf = x.astype(jnp.float32).reshape(-1)
    mean = jnp.mean(xf)
    var = jnp.var(xf)
    return mean, var


# client-axis oracle: per-client (mean, var) of a stacked (N, ...) leaf
batched_tensor_stats = jax.vmap(tensor_stats)


def param_distribution(params, *, use_pallas: bool = False):
    """Returns a feature vector (2 * n_tensors,) of per-tensor
    [mean, log1p(var)] pairs in a deterministic path order.

    ``log1p(var)`` rather than raw variance so k-means distances are not
    dominated by a single high-variance tensor (scale robustness).

    One client is the N=1 case of the swarm feature pass, so this is
    row 0 of ``_swarm_features`` on a singleton-stacked tree — a single
    copy of the feature logic that cannot drift from the batched path.
    """
    stacked = jax.tree.map(lambda x: x[None], params)
    return _swarm_features(stacked, use_pallas=use_pallas)[0]


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _swarm_features(stacked_params, *, use_pallas: bool):
    if use_pallas:
        from repro.kernels import ops as kops
        stat_fn = kops.param_stats_batched
    else:
        stat_fn = batched_tensor_stats
    pairs = sorted(tree_paths_and_leaves(stacked_params), key=lambda kv: kv[0])
    cols = []
    for _, leaf in pairs:
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        m, v = stat_fn(leaf)
        cols.append(m)
        cols.append(jnp.log1p(v))
    return jnp.stack(cols, axis=1)                       # (N, 2*T)


def swarm_distribution_matrix(stacked_params, n_clients: int = None, *,
                              use_pallas: bool = False):
    """Feature matrix (n_clients, F) from a client-stacked pytree —
    what the coordinator receives each round.

    All (client, tensor) [mean, log1p(var)] features are computed in a
    single jit'd pass over the stacked pytree: the jnp path vmaps
    ``tensor_stats`` over the client axis, the Pallas path reduces each
    stacked leaf on an (N, n_blocks) grid — one device program for the
    whole swarm instead of O(N·T) host dispatches."""
    if n_clients is not None:
        lead = jax.tree.leaves(stacked_params)[0].shape[0]
        if lead != n_clients:
            raise ValueError(
                f"stacked_params has client axis {lead} but n_clients="
                f"{n_clients}; slice the pytree to the requested subset")
    with jax.named_scope("bso.stat_upload"):
        return _swarm_features(stacked_params, use_pallas=use_pallas)


def swarm_distribution_matrix_loop(stacked_params, n_clients: int, *,
                                   use_pallas: bool = False):
    """The pre-batching coordinator: a host loop over clients with a
    per-tensor eager dispatch per stat — O(N·T) tiny device programs.
    Kept as the parity oracle for the batched path and as the 'before'
    side of ``benchmarks/cluster_ablation.coordinator_bench``.

    Deliberately does NOT share ``_swarm_features``: an oracle that
    routes through the code it checks can't catch bugs in the shared
    feature logic, and a baseline that jit-fuses per client would
    misrepresent the old dispatch count."""
    if use_pallas:
        from repro.kernels import ops as kops
        stat_fn = kops.param_stats
    else:
        stat_fn = tensor_stats
    rows = []
    for i in range(n_clients):
        client = jax.tree.map(lambda x: x[i], stacked_params)
        pairs = sorted(tree_paths_and_leaves(client), key=lambda kv: kv[0])
        feats = []
        for _, leaf in pairs:
            if not jnp.issubdtype(leaf.dtype, jnp.floating):
                continue
            m, v = stat_fn(leaf)
            feats.append(m)
            feats.append(jnp.log1p(v))
        rows.append(jnp.stack(feats))
    return jnp.stack(rows)


def upload_bytes(params) -> int:
    """Bytes a client uploads per round under BSO-SL (the stats)."""
    n_tensors = sum(1 for _, l in tree_paths_and_leaves(params)
                    if jnp.issubdtype(l.dtype, jnp.floating))
    return 2 * n_tensors * 4


def full_params_bytes(params) -> int:
    """Bytes a client would upload under FedAvg / blockchain SL."""
    return int(sum(l.size * l.dtype.itemsize for _, l in tree_paths_and_leaves(params)))
