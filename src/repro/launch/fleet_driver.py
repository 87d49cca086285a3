"""End-to-end multi-round BSO-SL on the pod mesh — the fleet driver.

This is the first surface where the WHOLE paper protocol runs in the
fleet regime rather than as a one-step lowering artifact: the round
program (``engine.make_fleet_round`` via ``swarm_fleet.fleet_setup``)
is compiled ONCE on the mesh, and the driver then closes the paper's
coordinator loop for R rounds:

  1. execute the fused fleet step — Eq. 2 on the incoming cluster
     decision, local SGD on the uploaded round batch, in-program val
     eval and distribution-stat upload (one executable, donated
     params/opt buffers, zero retraces),
  2. pull ONLY the tiny :class:`~repro.core.engine.FleetRoundOut`
     (the (N, 2·#tensors) stat matrix + (N,) val scores) to host,
  3. run the host-side coordinator — k-means on the stats plus the
     numpy ``brain_storm`` oracle, the paper's neighbour-assignment
     server (§III.B/C) — and feed the resulting ``clusters`` into the
     next round's donated buffers.

Because the round program aggregates FIRST (see
:func:`repro.core.engine.make_fleet_round`), R driver rounds execute
exactly the sim engine's protocol sequence (train → eval → stats →
coordinator → Eq. 2, R times) with the final Eq. 2 left pending on the
mesh. Parity with ``engine.run_rounds`` is therefore *statistical*,
not bitwise: the fleet samples batches host-side and the coordinator
consumes different RNG streams (numpy ``brain_storm`` vs the engine's
``brain_storm_jax``) — the same documented caveat as the existing
numpy-oracle parity (``tests/test_engine.py``). The per-round
trajectory property is pinned in ``tests/test_fleet.py``.

Unit scale (the 8-device CPU stand-in, small CNN clients) runs the
identical driver code: ``make_unit_fleet`` + :func:`run_fleet` is both
the tier-1 smoke and the traffic benchmark behind ``BENCH_fleet.json``
(``python -m benchmarks.comm_scaling --fleet``).

CLI (``--devices 8`` forces the 8-device CPU stand-in; without it the
driver uses the backend's own devices and prints which it ran on)::

    PYTHONPATH=src python -m repro.launch.fleet_driver --rounds 3 --devices 8
"""
from __future__ import annotations

import argparse
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import OptimizerConfig, asdict
from repro.checkpoint import save_checkpoint
from repro.core.aggregation import (cluster_fedavg, cluster_fedavg_masked,
                                    singleton_assignments)
from repro.core.bso import brain_storm
from repro.core.engine import make_batch, make_client_eval, stack_eval_split
from repro.core.kmeans import kmeans
from repro.data.dr import bucket_clients, make_dr_swarm_data, scale_table
from repro.launch.comm import fleet_round_comm, hier_round_comm
from repro.launch.mesh import make_fleet_mesh
from repro.launch.swarm_fleet import fleet_setup, force_host_device_count
from repro.models import build_model
from repro.optim.optimizers import make_optimizer
from repro.sharding import use_sharding
from repro.utils.compile_cache import use_compile_cache

# ------------------------------------------------------- host coordinator


_jit_kmeans = jax.jit(kmeans, static_argnames=("k", "iters"))


def host_coordinator(stats, val_acc, *, k: int, p1: float, p2: float,
                     kmeans_iters: int = 20, seed: int = 0,
                     round_idx: int = 0):
    """The paper's neighbour-assignment server, as a pure host function.

    Deterministic in ``(stats, val_acc, seed, round_idx)``: the k-means
    key is ``fold_in(PRNGKey(seed), round_idx)`` and the brain-storm
    stream is ``default_rng([seed, round_idx])``, so replaying a round's
    uploaded stats reproduces its cluster decision bit-for-bit (the
    determinism contract ``tests/test_fleet.py`` pins). Reuses the sim
    engine's k-means and the numpy ``brain_storm`` oracle — O(clients)
    work on a (N, 2·#tensors) matrix, negligible next to the round step.

    Returns ``(assignments, centers, events)`` — the (N,) int32 cluster
    decision to feed into the NEXT round's Eq. 2, the (k,) center client
    ids, and the human-readable BSA event log.
    """
    key = jax.random.fold_in(jax.random.PRNGKey(seed), round_idx)
    _, a0 = _jit_kmeans(key, jnp.asarray(stats, jnp.float32), k=k,
                        iters=kmeans_iters)
    rng = np.random.default_rng([seed, round_idx])
    plan = brain_storm(rng, np.asarray(a0), np.asarray(val_acc), k, p1, p2)
    return (plan.assignments.astype(np.int32),
            plan.centers.astype(np.int32), plan.events)


def _hier_val_means(counts, valsums):
    """Per-summary-row mean val accuracy; empty rows (a pod-cluster that
    captured no reporting clients) get -1.0 — inert under the BSA's
    best-score ranking, never a center."""
    counts = np.asarray(counts, np.float32)
    return np.where(counts > 0,
                    np.asarray(valsums, np.float32)
                    / np.maximum(counts, np.float32(1e-9)),
                    np.float32(-1.0)).astype(np.float32)


def host_hier_coordinator(centroids, counts, valsums, *, k: int, p1: float,
                          p2: float, kmeans_iters: int = 20, seed: int = 0,
                          round_idx: int = 0):
    """The two-tier coordinator's global tier — O(pods), not O(clients).

    Mirrors :func:`host_coordinator` (same per-round key/rng streams, so
    a round replays bit-for-bit from its pulled summaries) but consumes
    the ``S = pods * k_local`` pod-cluster summaries of
    :class:`~repro.core.engine.HierRoundOut` instead of per-client rows:
    WEIGHTED k-means over the pod centroids (weights = reporting-member
    counts, so an empty summary row anchors nothing) and the numpy
    ``brain_storm`` over the per-row mean val scores (empty rows -1.0,
    inert). Returns ``(g, centers, events)`` — the (S,) pod-cluster ->
    global-cluster map the round program composes in-program via
    ``g[a_local]``, and the (k,) center *summary-row* ids (not client
    ids — the host never sees clients on this surface).
    """
    key = jax.random.fold_in(jax.random.PRNGKey(seed), round_idx)
    w = jnp.asarray(counts, jnp.float32)
    _, a0 = _jit_kmeans(key, jnp.asarray(centroids, jnp.float32), k=k,
                        iters=kmeans_iters, weights=w)
    rng = np.random.default_rng([seed, round_idx])
    plan = brain_storm(rng, np.asarray(a0), _hier_val_means(counts, valsums),
                       k, p1, p2)
    return (plan.assignments.astype(np.int32),
            plan.centers.astype(np.int32), plan.events)


# -------------------------------------------------------- fault injection


# fault draws get their own host RNG stream: a 4-element seed can never
# collide with the coordinator's [seed, round] or the batch sampler's
# [seed, round, client] streams
_FAULT_STREAM_TAG = (0xFA, 0x17)


@dataclass(frozen=True)
class FleetFaults:
    """Host-side fault-injection regime for :func:`run_fleet`.

    ``drop_rate``      — per-round Bernoulli probability that a client
                         drops: no local phase (masked no-op on device),
                         no report to the coordinator, zero (or decayed)
                         weight in the next Eq. 2.
    ``straggler_rate`` — probability that a *non-dropped* client
                         straggles: it trains this round but its report
                         misses the coordinator deadline (the
                         coordinator falls back to its last-seen stats).
    ``delay_s``        — the straggler-delay model: each straggler's
                         report is late by this many (simulated) wall
                         seconds; logged per round as ``sim_delay_s``,
                         never slept.
    ``stale_decay``    — λ of the staleness-weighted Eq. 2: an absent
                         client keeps weight |D_h|·λ^staleness instead
                         of 0 (λ=0 is the hard participation mask —
                         0^0 == 1 keeps fresh clients at full weight).
    ``quorum``         — coordinator quorum Q: the coordinator only
                         recomputes the cluster decision when ≥ Q
                         clients report this round; below quorum it
                         re-applies the previous decision (round 0's
                         singleton fallback included) and the round is
                         logged ``coordinated=False``.

    All draws are deterministic in ``(seed, round_idx)`` via a dedicated
    ``default_rng`` stream, so a fault schedule replays bit-for-bit —
    the determinism contract ``tests/test_churn.py`` pins.
    """
    drop_rate: float = 0.0
    straggler_rate: float = 0.0
    delay_s: float = 0.0
    stale_decay: float = 0.0
    quorum: int = 0

    @property
    def active(self) -> bool:
        return (self.drop_rate > 0 or self.straggler_rate > 0
                or self.quorum > 0)


def draw_faults(faults: FleetFaults, n_clients: int, seed: int,
                round_idx: int):
    """One round's fault draw: ``(present, straggler)`` bool (N,) arrays.
    Stragglers are drawn among present clients only (a dropped client
    has nothing to be late with)."""
    rng = np.random.default_rng([seed, round_idx, *_FAULT_STREAM_TAG])
    present = rng.random(n_clients) >= faults.drop_rate
    straggler = present & (rng.random(n_clients) < faults.straggler_rate)
    return present, straggler


# ------------------------------------------------------------- the driver


@dataclass
class FleetRoundLog:
    """One driver round: the protocol artifacts pulled to host."""
    round: int
    mean_val_acc: float                # Eq. 3 over the val split
    val_acc: np.ndarray                # (N,)
    train_loss: float
    stats: np.ndarray                  # (N, 2*#tensors) §III.B upload
    assignments: np.ndarray            # (N,) decision FROM this round's
    #                                    stats (applied next round)
    centers: np.ndarray                # (k,) BSA center client ids
    applied_clusters: np.ndarray       # (N,) decision fed INTO this round
    events: List[str]
    wall_s: float
    coord_s: float
    # churn-regime fields (defaults = the fault-free run)
    present: Optional[np.ndarray] = None    # (N,) trained this round
    reported: Optional[np.ndarray] = None   # (N,) report met the deadline
    staleness: Optional[np.ndarray] = None  # (N,) rounds since last
    #                                         participation, post-round
    coordinated: bool = True           # False on a quorum miss (decision
    #                                    re-applied, not recomputed)
    sim_delay_s: float = 0.0           # straggler-delay model, simulated
    # hier-regime fields: on the two-tier surface `stats` holds the
    # (S, 2*#tensors) pod-cluster centroids, `val_acc`/`assignments`/
    # `centers` are per summary ROW (S = pods * k_local), and these two
    # complete the pulled upload (the coordinator replay inputs)
    counts: Optional[np.ndarray] = None     # (S,) reporting-member counts
    valsums: Optional[np.ndarray] = None    # (S,) summed member val accs


@dataclass
class FleetRunResult:
    history: List[FleetRoundLog]
    n_compiles: int                    # always 1 — the acceptance property
    comm: dict                         # per-round ledger (launch.comm)
    params: Any                        # final client-stacked params (on mesh)
    compile_s: float = 0.0
    meta: dict = field(default_factory=dict)
    compiled: Any = None               # the round executable (its HLO)

    @property
    def mean_val_accs(self):
        return [r.mean_val_acc for r in self.history]


def make_unit_fleet(n_clients: int = 8, *, arch: str = "squeezenet-dr",
                    image_size: int = 16, data_scale: int = 16,
                    seed: int = 0, lr: float = 2e-3):
    """Unit-scale fleet: the first ``n_clients`` Table-I clinics, one
    per pod slot of :func:`make_fleet_mesh` (one clinic per device on
    the 8-device CPU stand-in). Returns ``(model, opt, mesh,
    clients_data)`` — the arguments :func:`run_fleet` wants."""
    table = scale_table(data_scale)[:, :n_clients]
    clients = make_dr_swarm_data(image_size=image_size, seed=seed,
                                 table=table)
    model = build_model(get_config(arch))
    opt = make_optimizer(OptimizerConfig(name="adam", lr=lr))
    return model, opt, make_fleet_mesh(len(clients)), clients


def _sample_round_batch(model_cfg, clients_data, n_rows: int, seed: int,
                        round_idx: int):
    """Host-side per-round batch upload: every client draws ``n_rows``
    uniform-with-replacement rows from its own train split — the same
    distribution as the engine's on-device per-step sampler, stacked as
    the (N, n_rows, ...) round batch the fleet step slices per step."""
    Xs, ys = [], []
    for i, c in enumerate(clients_data):
        rng = np.random.default_rng([seed, round_idx, i])
        X, y = c["train"]
        idx = rng.integers(0, len(y), size=n_rows)
        Xs.append(X[idx])
        ys.append(y[idx])
    return make_batch(model_cfg, np.stack(Xs), np.stack(ys))


def export_fleet_checkpoint(path, model, sparams, clusters, weights, *,
                            round_idx: int, n_clusters: int,
                            mean_val_acc: float = 0.0, present=None):
    """Serialize the swarm state for ``repro.serve``.

    Applies the round's pending Eq. 2 (the aggregation the NEXT round
    would fold in) so the checkpoint holds each client's cluster
    aggregate, then saves the client-stacked tree with a manifest
    ``extra`` sufficient to rebuild the model serve-side with no
    training code: the full ``ModelConfig`` asdict, client count,
    |D_h| weights and the cluster decision.

    ``present`` (optional (N,) bool) switches the pending Eq. 2 onto the
    churn-masked variant with ``weights`` taken as the *effective*
    (staleness-decayed) weights — the exact aggregation the next driver
    round would execute, so a churn-regime checkpoint matches what the
    swarm would actually serve. ``None`` keeps the plain aggregate.
    """
    w = jnp.asarray(weights, jnp.float32)
    if present is None:
        agg = cluster_fedavg(sparams, jnp.asarray(clusters), w,
                             k=len(np.asarray(clusters)))
    else:
        agg = cluster_fedavg_masked(sparams, jnp.asarray(clusters), w,
                                    jnp.asarray(present, bool),
                                    k=len(np.asarray(clusters)))
    save_checkpoint(path, agg, step=round_idx + 1, extra={
        "model_config": asdict(model.cfg),
        "n_clients": int(len(np.asarray(clusters))),
        "client_weights": np.asarray(weights, np.float32).tolist(),
        "assignments": np.asarray(clusters, np.int32).tolist(),
        "n_clusters": int(n_clusters),
        "mean_val_acc": float(mean_val_acc),
    })


def run_fleet(model, opt, mesh, clients_data, *, rounds: int,
              local_steps: int = 4, batch_size: int = 8, lr: float = 2e-3,
              n_clusters: int = 3, p1: float = 0.9, p2: float = 0.8,
              kmeans_iters: int = 20, seed: int = 0,
              use_pallas_stats: bool = False, eval_batch: int = 64,
              eval_buckets: int = 0, bucket_strategy: str = "pow2",
              ckpt_path=None, ckpt_every: int = 0,
              faults: Optional[FleetFaults] = None,
              hier_k_local: int = 0,
              verbose: bool = False) -> FleetRunResult:
    """Drive ``rounds`` full BSO-SL rounds on ``mesh`` with exactly ONE
    compiled fleet-round executable.

    The round step is lowered and compiled once (AOT) with donated
    params/opt buffers; every round re-invokes the same executable with
    the freshly uploaded batch and the previous round's host cluster
    decision. Round 0 feeds ``singleton_assignments`` (Eq. 2 is the
    bitwise identity), so the executed protocol sequence matches the
    sim engine's round for round — see the module docstring.

    ``eval_buckets > 0`` switches val scoring onto the bucketed ragged
    layout: clients are grouped into size buckets
    (:func:`repro.data.dr.bucket_clients` on the val-split sizes), each
    bucket's eval stack is padded only to its own ceiling, and the
    driver compiles ONE fixed-shape eval program per bucket signature
    (round program built ``with_loss`` — no rectangular val stack rides
    the mesh). The compile budget becomes ``1 + n_buckets`` executables
    total, still zero per-round retraces, and the per-client accuracies
    are identical to the in-program rectangular eval (same
    post-local-phase params, same masked reduction —
    ``tests/test_fleet.py`` pins the parity).

    ``faults`` (a :class:`FleetFaults` with any knob active) switches
    the driver onto the churn regime — still ONE compiled executable:
    the round program is built ``with_churn`` (two extra (N,) bool
    operands) and the host injects per-round Bernoulli drops and
    straggler delays, applies the quorum rule to the coordinator, and
    carries the staleness counters that decay the Eq. 2 weights. Because
    the fleet aggregates FIRST, round ``r``'s incoming Eq. 2 uses round
    ``r-1``'s presence mask and post-round staleness — exactly the sim
    engine's churn semantics shifted by the pending-aggregation offset.
    An all-knobs-off ``FleetFaults()`` (or ``None``) keeps the
    churn-free program.

    ``hier_k_local > 0`` switches the driver onto the HIERARCHICAL
    two-tier regime (exclusive with ``eval_buckets`` — the hier round
    carries its own in-program eval): each mesh pod runs a local
    ``hier_k_local``-means over its clients' stats in-program, the
    driver pulls ONLY the O(pods * k_local)
    :class:`~repro.core.engine.HierRoundOut` summaries, and
    :func:`host_hier_coordinator` answers with the (S,) pod-cluster ->
    global-cluster map ``g`` that the next round composes on-mesh via
    ``g[a_local]`` (``a_local`` is fed back device-to-device, never
    pulled until a checkpoint export). Host traffic and host compute
    become O(pods), not O(clients) — the scaling claim
    ``BENCH_hier.json`` measures. Under ``faults`` the straggler
    exclusion moves IN-PROGRAM (a third ``report`` mask gates the pod
    k-means and summary sums); there is no host-side last-seen report
    cache — that cache is O(clients), the very thing this regime
    removes — so a straggler's stats simply sit out the round instead
    of being replayed stale (documented semantic difference from the
    flat churn regime).
    """
    N = len(clients_data)
    if n_clusters > N:
        raise ValueError(f"n_clusters={n_clusters} > n_clients={N}")
    hier = hier_k_local > 0
    bucketed = eval_buckets > 0
    if hier and bucketed:
        raise ValueError("hier_k_local and eval_buckets are exclusive "
                         "driver regimes (the hier round carries its own "
                         "in-program eval)")
    n_pods = int(mesh.shape["pod"]) if hier else 0
    S = n_pods * hier_k_local
    if hier and n_clusters > S:
        raise ValueError(
            f"n_clusters={n_clusters} > pods*k_local={S}: the global tier "
            "clusters the summary rows — raise hier_k_local or use more "
            "pods")
    churn = faults is not None and faults.active
    program = fleet_setup(model, opt, mesh, k=N, n_local_steps=local_steps,
                          use_pallas_stats=use_pallas_stats,
                          with_eval=not bucketed and not hier,
                          with_loss=bucketed,
                          donate=True, spmd="shard_map",
                          with_churn=churn, hier_k_local=hier_k_local)
    n_masks = (3 if hier else 2) if churn else 0
    in_sh = (program.in_shardings[:-n_masks] if n_masks
             else program.in_shardings)
    if hier:
        _, _, bsh, vsh, lsh, gsh, ush, csh, ash, kmsh, wsh = in_sh
    elif bucketed:
        _, _, bsh, lsh, csh, wsh = in_sh
    else:
        _, _, bsh, vsh, lsh, csh, wsh = in_sh
    msh = program.in_shardings[-1] if churn else None
    lr_arr = jax.device_put(jnp.float32(lr), lsh)

    with mesh, use_sharding(mesh, program.rules):
        keys = jax.random.split(jax.random.PRNGKey(seed), N)
        psh, osh = program.in_shardings[0], program.in_shardings[1]
        sparams = jax.jit(lambda ks: jax.vmap(model.init)(ks),
                          out_shardings=psh)(keys)
        sopt = jax.jit(lambda p: jax.vmap(opt.init)(p),
                       out_shardings=osh)(sparams)
        eval_progs = []
        if bucketed:
            # one fixed-shape eval program per bucket: gather the
            # bucket's client params, score its own-ceiling val stack
            rep = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            groups = bucket_clients(
                [len(c["val"][1]) for c in clients_data],
                max_buckets=eval_buckets, strategy=bucket_strategy)
            ev = make_client_eval(model)
            for ids in groups:
                ids_arr = np.asarray(ids)
                val_b = jax.device_put(
                    stack_eval_split(model.cfg,
                                     [clients_data[i] for i in ids],
                                     "val", batch=eval_batch), rep)
                fn = jax.jit(lambda p, v, _ids=ids_arr: ev(
                    jax.tree.map(lambda x: x[_ids], p), v))
                eval_progs.append((ids_arr, val_b, fn))
        else:
            val = jax.device_put(
                stack_eval_split(model.cfg, clients_data, "val",
                                 batch=eval_batch), vsh)
        base_w = np.asarray([c["n_train"] for c in clients_data],
                            np.float32)
        weights = jax.device_put(base_w, wsh)
        clusters = np.asarray(singleton_assignments(N))
        if hier:
            # device-resident coordinator plumbing: the O(N) singleton
            # fallback and the assignment feedback never cross the host
            # boundary — only the (S,) decision g rides back per round
            clusters0_dev = jax.device_put(clusters.astype(np.int32), csh)
            a_prev = jax.device_put(np.zeros(N, np.int32), ash)
            g = np.zeros(S, np.int32)

        # churn-regime host state: staleness counters (rounds since last
        # participation), the previous round's presence (the pending
        # Eq. 2's receive mask — all-ones before round 0), and the
        # coordinator's last-seen report cache for stragglers (flat
        # regime only — the hier surface excludes stragglers in-program)
        staleness = np.zeros(N, np.int32)
        prev_present = np.ones(N, bool)
        have_cache = np.zeros(N, bool)
        last_stats, last_val = None, None
        centers = np.full(n_clusters, -1, np.int32)   # no decision yet

        def eff_weights():
            # |D_h| * λ^staleness — λ=0 is the hard mask (0^0 == 1
            # keeps fresh clients at full weight, matching the engine's
            # jnp.power semantics bitwise for integer exponents)
            return base_w * np.power(np.float32(faults.stale_decay),
                                     staleness.astype(np.float32))

        def put_batch(r):
            batch = _sample_round_batch(model.cfg, clients_data,
                                        local_steps * batch_size, seed, r)
            return jax.device_put(batch, bsh)

        # ONE lowering -> ONE executable for every round
        t0 = time.perf_counter()
        batch0 = put_batch(0)
        mask_ops = ()
        if churn:
            ones = jax.device_put(np.ones(N, bool), msh)
            mask_ops = (ones,) * n_masks
        if hier:
            lowered = program.jit_fn.lower(
                sparams, sopt, batch0, val, lr_arr,
                jax.device_put(g, gsh), jax.device_put(jnp.asarray(False),
                                                       ush),
                clusters0_dev, a_prev,
                jax.device_put(jax.random.fold_in(jax.random.PRNGKey(seed),
                                                  0), kmsh),
                weights, *mask_ops)
        elif bucketed:
            lowered = program.jit_fn.lower(
                sparams, sopt, batch0, lr_arr,
                jax.device_put(clusters, csh), weights, *mask_ops)
        else:
            lowered = program.jit_fn.lower(
                sparams, sopt, batch0, val, lr_arr,
                jax.device_put(clusters, csh), weights, *mask_ops)
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        batch_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(batch0))
        params_abs = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        if hier:
            comm = hier_round_comm(compiled, params_abs, N, n_pods=n_pods,
                                   k_local=hier_k_local,
                                   batch_bytes=batch_bytes)
        else:
            comm = fleet_round_comm(compiled, params_abs, N,
                                    batch_bytes=batch_bytes)

        history = []
        for r in range(rounds):
            t0 = time.perf_counter()
            # round 0 re-uploads the batch the lowering used (sampling is
            # deterministic per (seed, r)) so every round's wall_s covers
            # the same work: sample + upload + round step + stat pull
            batch = put_batch(r)
            applied = g.copy() if hier else clusters
            extra = ()
            present = straggler = reported = None
            if churn:
                present, straggler = draw_faults(faults, N, seed, r)
                reported = present & ~straggler
                # the incoming Eq. 2 is the PREVIOUS round's pending
                # aggregation: its receive mask is last round's presence
                # and its weights carry last round's post-round staleness
                weights = jax.device_put(eff_weights(), wsh)
                extra = (jax.device_put(present, msh),
                         jax.device_put(prev_present, msh))
                if hier:
                    # third mask: stragglers train but miss the summary
                    # deadline — excluded from the pod k-means in-program
                    extra = extra + (jax.device_put(reported, msh),)
            if hier:
                sparams, sopt, out = compiled(
                    sparams, sopt, batch, val, lr_arr,
                    jax.device_put(g, gsh),
                    jax.device_put(jnp.asarray(r > 0), ush),
                    clusters0_dev, a_prev,
                    jax.device_put(
                        jax.random.fold_in(jax.random.PRNGKey(seed), r),
                        kmsh),
                    weights, *extra)
                # the ONLY device->host pull: the O(pods) summaries —
                # a_local stays on-mesh as next round's a_prev operand
                stats = np.asarray(out.centroids)
                counts = np.asarray(out.counts)
                valsums = np.asarray(out.valsums)
                val_acc = _hier_val_means(counts, valsums)
                train_loss = float(out.train_loss)
                hier_mean_val = float(out.mean_val)
                a_prev = out.a_local
            elif bucketed:
                sparams, sopt, stats_dev, loss_dev = compiled(
                    sparams, sopt, batch, lr_arr,
                    jax.device_put(applied, csh), weights, *extra)
                stats = np.asarray(stats_dev)
                # per-bucket scoring of the returned post-local-phase
                # params — the same protocol point as the in-program eval
                val_acc = np.zeros(N, np.float32)
                for ids_arr, val_b, fn in eval_progs:
                    val_acc[ids_arr] = np.asarray(fn(sparams, val_b))
                train_loss = float(loss_dev)
            else:
                sparams, sopt, out = compiled(
                    sparams, sopt, batch, val, lr_arr,
                    jax.device_put(applied, csh), weights, *extra)
                # the ONLY device->host pull: the tiny FleetRoundOut
                stats = np.asarray(out.stats)
                val_acc = np.asarray(out.val_acc)
                train_loss = float(out.train_loss)
            t1 = time.perf_counter()
            coordinated = True
            events: List[str] = []
            if churn:
                # post-round state: presence resets staleness, absence
                # accrues it; this round's mask gates the NEXT Eq. 2
                staleness = np.where(present, 0, staleness + 1) \
                    .astype(np.int32)
                prev_present = present
                n_rep = int(reported.sum())
            if hier:
                if churn and faults.quorum and n_rep < faults.quorum:
                    coordinated = False
                    events = [f"quorum miss: {n_rep}/{N} reported "
                              f"< Q={faults.quorum}; previous pod-cluster "
                              "map re-applied"]
                else:
                    g, centers, events = host_hier_coordinator(
                        stats, counts, valsums, k=n_clusters, p1=p1,
                        p2=p2, kmeans_iters=kmeans_iters, seed=seed,
                        round_idx=r)
            elif churn:
                # the coordinator sees fresh reports only from clients
                # that met the deadline; stragglers/dropped fall back to
                # their last-seen report (a dropped client's params are
                # frozen, so its freshly computed stats equal its stale
                # ones — no information leak either way)
                stats_used, val_used = stats.copy(), val_acc.copy()
                if last_stats is not None:
                    miss = ~reported & have_cache
                    stats_used[miss] = last_stats[miss]
                    val_used[miss] = last_val[miss]
                else:
                    last_stats = np.zeros_like(stats)
                    last_val = np.zeros_like(val_acc)
                last_stats[reported] = stats[reported]
                last_val[reported] = val_acc[reported]
                have_cache |= reported
                if faults.quorum and n_rep < faults.quorum:
                    # quorum miss: re-apply the previous decision (round
                    # 0's singleton fallback included) — deterministic,
                    # and the skipped coordinator stream is simply never
                    # drawn for this round_idx
                    coordinated = False
                    events = [f"quorum miss: {n_rep}/{N} reported "
                              f"< Q={faults.quorum}; previous cluster "
                              "decision re-applied"]
                else:
                    clusters, centers, events = host_coordinator(
                        stats_used, val_used, k=n_clusters, p1=p1, p2=p2,
                        kmeans_iters=kmeans_iters, seed=seed, round_idx=r)
            else:
                clusters, centers, events = host_coordinator(
                    stats, val_acc, k=n_clusters, p1=p1, p2=p2,
                    kmeans_iters=kmeans_iters, seed=seed, round_idx=r)
            t2 = time.perf_counter()
            log = FleetRoundLog(
                round=r,
                mean_val_acc=hier_mean_val if hier
                else float(val_acc.mean()),
                val_acc=val_acc, train_loss=train_loss,
                stats=stats,
                assignments=g.copy() if hier else clusters,
                centers=centers,
                applied_clusters=applied, events=list(events),
                wall_s=t1 - t0, coord_s=t2 - t1,
                present=present, reported=reported,
                staleness=staleness.copy() if churn else None,
                coordinated=coordinated,
                sim_delay_s=float(faults.delay_s) if churn
                and bool(straggler.any()) else 0.0,
                counts=counts if hier else None,
                valsums=valsums if hier else None)
            history.append(log)
            if ckpt_path and ckpt_every and (r + 1) % ckpt_every == 0:
                # when ckpt_every divides rounds, the _r{rounds} export
                # is bitwise the final export below — same params, same
                # decision, same (effective) weights. A hier export is
                # the ONE place the (N,) assignments are materialised on
                # host: compose g[a_local] from the device feedback.
                export_fleet_checkpoint(
                    f"{ckpt_path}_r{r + 1}", model, sparams,
                    g[np.asarray(a_prev)] if hier else clusters,
                    eff_weights() if churn else base_w, round_idx=r,
                    n_clusters=n_clusters, mean_val_acc=log.mean_val_acc,
                    present=present if churn else None)
            if verbose:
                flag = "" if coordinated else " [quorum miss]"
                decision = g if hier else clusters
                print(f"[fleet] round {r}: val_acc={log.mean_val_acc:.3f} "
                      f"loss={log.train_loss:.3f} "
                      f"clusters={np.bincount(decision, minlength=n_clusters)}"
                      f" events={len(events)} wall={log.wall_s:.2f}s{flag}")

    if ckpt_path:
        if history:
            # final export: fold in the pending Eq. 2 (see module
            # docstring) — under churn, the masked variant with the
            # staleness-decayed weights the next round would apply. On
            # the hier surface the (N,) decision is composed here from
            # the device-resident feedback (the one a_local pull).
            export_fleet_checkpoint(
                ckpt_path, model, sparams,
                g[np.asarray(a_prev)] if hier
                else history[-1].assignments,
                eff_weights() if churn else base_w, round_idx=rounds - 1,
                n_clusters=n_clusters,
                mean_val_acc=history[-1].mean_val_acc,
                present=prev_present if churn else None)
        else:
            # rounds=0 used to silently skip the export; save the
            # initial swarm under the identity Eq. 2 instead so the
            # caller always gets the checkpoint it asked for
            warnings.warn(
                "run_fleet(rounds=0) with ckpt_path: no rounds executed "
                "— exporting the initial (untrained) swarm params under "
                "the singleton identity Eq. 2", stacklevel=2)
            export_fleet_checkpoint(
                ckpt_path, model, sparams, clusters, base_w,
                round_idx=-1, n_clusters=n_clusters, mean_val_acc=0.0)
    meta = dict(n_clients=N, rounds=rounds, local_steps=local_steps,
                batch_size=batch_size, lr=lr, n_clusters=n_clusters, p1=p1,
                p2=p2, seed=seed, mesh_shape=dict(mesh.shape),
                n_devices=mesh.size,
                eval_buckets=len(eval_progs) if bucketed else 0,
                hier=None if not hier else {
                    "k_local": hier_k_local, "n_pods": n_pods,
                    "summary_rows": S},
                faults=None if faults is None else {
                    "drop_rate": faults.drop_rate,
                    "straggler_rate": faults.straggler_rate,
                    "delay_s": faults.delay_s,
                    "stale_decay": faults.stale_decay,
                    "quorum": faults.quorum})
    # measured, not asserted: the AOT `compiled` path performs exactly the
    # one .compile() above, and any (future) direct jit_fn dispatches
    # would land in its trace cache — so this catches a regression that
    # reintroduces per-round retracing. Bucketed eval adds exactly one
    # compiled program per bucket signature (their jit caches never grow
    # past 1 — same shapes every round).
    n_compiles = (1 + program.jit_fn._cache_size()
                  + sum(fn._cache_size() for _, _, fn in eval_progs))
    return FleetRunResult(history=history, n_compiles=n_compiles, comm=comm,
                          params=sparams, compile_s=compile_s, meta=meta,
                          compiled=compiled)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--data-scale", type=int, default=16)
    ap.add_argument("--image-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0,
                    help="force this many CPU stand-in devices (0 = use "
                         "the backend's own devices)")
    ap.add_argument("--pallas-stats", action="store_true")
    ap.add_argument("--eval-buckets", type=int, default=0,
                    help="bucket the val eval into at most this many "
                         "size buckets (0 = rectangular in-program eval)")
    ap.add_argument("--ckpt", default=None, metavar="PATH",
                    help="export the final aggregated swarm params "
                         "(npz + manifest) for repro.serve")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="also export every N rounds (PATH_r<N>)")
    ap.add_argument("--drop-rate", type=float, default=0.0,
                    help="per-round Bernoulli client-drop probability "
                         "(fault injection; 0 = churn-free)")
    ap.add_argument("--straggler-rate", type=float, default=0.0,
                    help="probability a present client reports late")
    ap.add_argument("--straggler-delay", type=float, default=0.0,
                    help="simulated straggler report delay in seconds "
                         "(logged, never slept)")
    ap.add_argument("--stale-decay", type=float, default=0.0,
                    help="λ of the staleness-weighted Eq. 2 "
                         "(0 = hard participation mask)")
    ap.add_argument("--quorum", type=int, default=0,
                    help="coordinator quorum Q: recompute clusters only "
                         "when >= Q clients report (0 = always)")
    ap.add_argument("--hier-k", type=int, default=0,
                    help="per-pod local k-means cluster count: > 0 "
                         "switches onto the two-tier O(pods) coordinator "
                         "(0 = flat O(clients))")
    args = ap.parse_args()
    if args.devices:
        force_host_device_count(args.devices)
    use_compile_cache()
    dev = jax.devices()
    print(f"[fleet] device: platform={dev[0].platform} "
          f"kind={dev[0].device_kind} count={len(dev)}")
    model, opt, mesh, clients = make_unit_fleet(
        args.clients, image_size=args.image_size,
        data_scale=args.data_scale, seed=args.seed)
    faults = FleetFaults(drop_rate=args.drop_rate,
                         straggler_rate=args.straggler_rate,
                         delay_s=args.straggler_delay,
                         stale_decay=args.stale_decay,
                         quorum=args.quorum)
    res = run_fleet(model, opt, mesh, clients, rounds=args.rounds,
                    local_steps=args.local_steps,
                    batch_size=args.batch_size, seed=args.seed,
                    use_pallas_stats=args.pallas_stats,
                    eval_buckets=args.eval_buckets,
                    ckpt_path=args.ckpt, ckpt_every=args.ckpt_every,
                    faults=faults if faults.active else None,
                    hier_k_local=args.hier_k,
                    verbose=True)
    if args.ckpt:
        print(f"[fleet] checkpoint -> {args.ckpt}.npz")
    coll = res.comm["eq2_collective_bytes"]["total"]
    if args.hier_k:
        up = res.comm["summary_upload_bytes"]
        what = (f"summary upload {up} B "
                f"({res.comm['summary_rows']} rows) to host")
    else:
        up = res.comm["stat_upload_bytes"]
        what = f"stat upload {up} B to host"
    print(f"[fleet] {res.meta['n_clients']} clients on "
          f"{res.meta['n_devices']} devices, {args.rounds} rounds, "
          f"{res.n_compiles} compile ({res.compile_s:.1f}s); per round: "
          f"{what}, Eq.2 collectives {coll} B/device")


if __name__ == "__main__":
    main()
