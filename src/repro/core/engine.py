"""Functional BSO-SL round engine: ONE jit'd program per round.

The paper's round (§III) — local SGD → distribution upload → k-means →
brain-storm aggregation — is expressed here as a pure function over an
explicit :class:`SwarmState` pytree::

    state, metrics = swarm_round(state, data, cfg)

Everything inside is traceable: local-training batches are sampled
on-device (`jax.random` gather over the device-resident stacked
dataset in :class:`SwarmData`), the coordinator runs the jax
``brain_storm_jax`` port, and Eq. 2 aggregation is the segment-sum
``cluster_fedavg``. A whole sim-regime round is therefore a single
device program, and :func:`run_rounds` scans it over rounds so a full
``fit`` is ONE program too.

Both regimes share this body:

* **sim** — :func:`swarm_round`; the stateful
  :class:`repro.core.swarm.SwarmTrainer` is a thin host wrapper.
* **fleet** — :func:`make_fleet_round` composes the same
  :func:`local_phase` + in-program distribution-stat upload
  (``param_stats_batched`` under ``use_pallas``) + ``cluster_fedavg``;
  only the O(clients) coordinator decision (k-means + brain storm)
  arrives from the host, matching the paper's neighbour-assignment
  server (see ``repro/launch/swarm_fleet.py``).

The round also carries a **method axis** (paper Table II): the four
comparison methods are parameterisations of this one body, realised as
the traced :class:`MethodParams` masks —

* ``centralized``  — every client samples the pooled global dataset
  (the "1 merged client" upper bound, batched over N replicas) and
  aggregates into one global model each round,
* ``local``        — singleton clusters: Eq. 2 is the bitwise identity,
* ``fedavg``       — one global cluster, no coordinator decision,
* ``bso-sl``       — the full k-means + brain-storm path.

Because the differences are traced data (a pooling flag and a fallback
assignment vector), ONE compiled program serves the whole axis:
:func:`run_sweep` vmaps :func:`run_rounds` over stacked
:class:`MethodParams` + per-method :class:`SwarmState`, sharing a
single device-resident :class:`SwarmData` — the paper's Table II grid
(4 methods x rounds programs) collapses to one executable.

The same move generalises to **hyper-parameter grids** (the knobs the
paper fixes without ablation — k=3, p1=0.9, p2=0.8): a
:class:`GridPoint` carries the BSO knobs (cluster count, p1, p2,
local-step and lr overrides) as traced per-row data on top of the
:class:`MethodParams` masks. The cluster count rides a masked
static-max path — ``cfg.n_clusters`` is the pad ``k_max``, k-means and
the brain storm mask clusters ``>= point.n_clusters`` — and the local
phase applies only the first ``point.local_steps`` updates. So
:func:`run_grid` vmaps :func:`run_rounds` over stacked
:class:`GridPoint` rows and a whole (k x p1 x p2) ablation lowers to
ONE executable too, again sharing one device-resident
:class:`SwarmData`. Each grid row is bitwise-equal to the serial
single-point program, and a padded-k row is bitwise-equal to a native
smaller-k run (``tests/test_grid.py``).

And to **scenarios**: real fleets churn — clients drop, lag, and
rejoin. :class:`ChurnParams` makes that a traced axis on the same one
program: a per-round participation mask (seeded Bernoulli dropout or an
explicit schedule) under which absent clients run masked no-op local
steps, keep their stale params through Eq. 2 (the masked
``cluster_fedavg_masked`` with an all-absent-cluster fallback), and
drop out of the k-means stats matrix (masked points ride the existing
empty-cluster reseed); a ``stale_decay`` knob turns hard masking into
staleness-weighted aggregation (weight ``|D_h| * decay^staleness``,
counters carried in :attr:`SwarmState.staleness`). ``dropout`` /
``stale_decay`` / ``churn_mask`` are :class:`GridPoint` axes, so a
dropout-robustness sweep is ONE executable; an all-ones mask is bitwise
the churn-free engine (``tests/test_churn.py``).

Contract summary (the stable public surface):

* :class:`SwarmState` — the complete mutable swarm (params, opt state,
  PRNG key, round counter, Eq. 2 sample weights), one pytree.
* :class:`SwarmData` — the device-resident fixed-shape dataset
  (padded train stack + sampling bounds + masked val stacks, one per
  group of clients that need the same number of eval microbatches).
* :class:`EngineConfig` — the static (hashable) round configuration;
  equal configs share one compiled program.
* :class:`MethodParams` / :class:`GridPoint` — traced per-row axes:
  what the paper varies, expressed as data instead of control flow.
* :func:`swarm_round` / :func:`run_rounds` / :func:`run_sweep` /
  :func:`run_grid` — one round / one fit / the Table-II axis / a
  hyper-parameter grid, each as ONE device program.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, SwarmConfig
from repro.core.aggregation import (cluster_fedavg, cluster_fedavg_masked,
                                    cluster_fedavg_psum,
                                    cluster_fedavg_psum_masked,
                                    singleton_assignments)
from repro.core.bso import brain_storm_jax
from repro.core.diststats import swarm_distribution_matrix
from repro.core.kmeans import kmeans
from repro.models.model import Model
from repro.optim.optimizers import Optimizer
from repro.train.steps import make_eval_step, make_train_step

# --------------------------------------------------------------------- state


class SwarmState(NamedTuple):
    """The complete mutable state of a swarm, as one pytree.

    Every field has a leading client axis N where applicable, so the
    state threads through jit/scan/donation without host round-trips.
    """
    params: Any                      # client-stacked model pytree (N, ...)
    opt_state: Any                   # client-stacked optimizer pytree
    key: Any                         # PRNG key driving sampling + BSA
    round: Any                       # () int32 round counter
    n_samples: Any                   # (N,) float32 |D_h| (Eq. 2 weights)
    staleness: Any = None            # (N,) int32 rounds since last
    #                                  participation (0 = participated
    #                                  this round) — the churn axis's
    #                                  carried counter; None on states
    #                                  predating the churn engine


@jax.tree_util.register_pytree_node_class
class SwarmData:
    """Device-resident, fixed-shape swarm dataset.

    train:   batch pytree with shape (N, n_max, ...); clients shorter
             than n_max are padded (pad rows are never sampled).
    train_n: (N,) int32 true train-set sizes — the sampling bound.
    val:     tuple of val groups, group g the clients that need the
             same number of eval microbatches, stacked (N_g, n_batches_g,
             batch, ...) with label=-1 masking (see
             :func:`stack_eval_split`), so no client carries an all-pad
             microbatch.
    val_ids: static tuple of per-group client-id tuples (ascending
             within a group; a partition of range(N)). Pytree aux data,
             the contract of :attr:`BucketedSwarmData.client_ids`: equal
             layouts share one compiled program. A swarm whose clients
             all need the same count is one group, ``range(N)``.
    """

    def __init__(self, train, train_n, val, val_ids):
        self.train = train
        self.train_n = train_n
        self.val = tuple(val)
        self.val_ids = tuple(tuple(int(i) for i in ids) for ids in val_ids)

    def tree_flatten(self):
        return (self.train, self.train_n, self.val), self.val_ids

    @classmethod
    def tree_unflatten(cls, aux, children):
        train, train_n, val = children
        return cls(train, train_n, val, aux)


@jax.tree_util.register_pytree_node_class
class BucketedSwarmData:
    """Size-bucketed, ragged-aware sibling of :class:`SwarmData`.

    A skewed swarm (paper Table I: clinic sizes 14..974) pays for the
    rectangular layout twice — every client's train stack and eval
    stack are padded to the *global* maximum. This layout groups
    clients into a few size buckets (:func:`repro.data.dr.
    bucket_clients`) and pads each bucket only to its own ceiling:

    train:      tuple of per-bucket batch pytrees, bucket b shaped
                (N_b, n_max_b, ...) — pad rows never sampled.
    val:        tuple of per-bucket stacked eval splits, bucket b
                shaped (N_b, n_batches_b, batch, ...) with label=-1
                masking (:func:`stack_eval_split` layout per bucket).
    train_n:    (N,) int32 true train sizes in ORIGINAL client order —
                the same global sampling bound as :class:`SwarmData`,
                so index draws are bitwise layout-independent.
    client_ids: static tuple of per-bucket client-id tuples (ascending
                within a bucket; a partition of range(N)). Static
                (pytree aux data), so per-bucket gathers/scatters trace
                to fixed-shape ops and equal layouts share one compiled
                program — the same static-shape discipline as
                :func:`run_grid`.

    The engine dispatches on the layout (:func:`sample_round_batch`,
    :func:`eval_swarm`): every :func:`swarm_round` / :func:`run_rounds`
    / :func:`run_sweep` / :func:`run_grid` entry point accepts either,
    and the bucketed results are BITWISE the rectangular ones (pinned
    in ``tests/test_bucket.py``) — sampling draws the identical global
    index tensor and eval drops only all-pad microbatches whose
    contribution is exactly +0.0.
    """

    def __init__(self, train, val, train_n, client_ids):
        self.train = tuple(train)
        self.val = tuple(val)
        self.train_n = train_n
        self.client_ids = tuple(tuple(int(i) for i in ids)
                                for ids in client_ids)

    @property
    def n_buckets(self) -> int:
        return len(self.client_ids)

    @property
    def val_ids(self):
        """The val groups are the buckets (:attr:`SwarmData.val_ids`)."""
        return self.client_ids

    def tree_flatten(self):
        return (self.train, self.val, self.train_n), self.client_ids

    @classmethod
    def tree_unflatten(cls, aux, children):
        train, val, train_n = children
        return cls(train, val, train_n, aux)


class RoundMetrics(NamedTuple):
    """Per-round outputs (all device scalars/arrays, scan-stackable)."""
    mean_val_acc: Any                # () — paper Eq. 3 on the val split
    val_acc: Any                     # (N,) per-client val accuracy
    train_loss: Any                  # () mean loss of the last local step
    assignments: Any                 # (N,) int32 post-BSA clusters
    centers: Any                     # (k,) int32 center client ids
    n_replaced: Any                  # () int32 BSA replacement events
    n_swapped: Any                   # () int32 BSA swap events
    present: Any = None              # (N,) bool participation mask of
    #                                  this round (all-ones when no
    #                                  churn axis is threaded)


class MethodParams(NamedTuple):
    """Traced per-method knobs — the Table-II method axis as data.

    Every field is a jax array (no python branches), so the four paper
    methods trace to the SAME program and :func:`run_sweep` can vmap
    over a stacked instance. ``base_assign`` is the aggregation plan
    used when the coordinator is masked off; the segment count is
    always N (see :func:`~repro.core.aggregation.cluster_fedavg`).
    """
    pool_data: Any        # () bool — sample minibatches from the pooled
                          #           global dataset (centralized)
    use_coord: Any        # () bool — take the k-means + brain-storm
                          #           assignments (bso-sl)
    base_assign: Any      # (N,) int32 — assignments when not use_coord:
                          #           arange(N) local, zeros fedavg/centr.


#: Paper Table II method axis, in table order.
SWEEP_METHODS = ("centralized", "local", "fedavg", "bso-sl")


def method_params(method: str, n_clients: int) -> MethodParams:
    """The :class:`MethodParams` row realising one paper method.

    The axis is a *controlled same-budget* comparison: every method —
    centralized included — runs the same (rounds x local_steps x
    batch) grid. The paper's centralized number relied on a step count
    scaled by the clinic count; ``baselines.train_centralized`` keeps
    that paper-budget oracle for reference (table2 reports both).
    """
    if method not in SWEEP_METHODS:
        raise ValueError(f"unknown method {method!r}; one of {SWEEP_METHODS}")
    zeros = jnp.zeros((n_clients,), jnp.int32)
    return MethodParams(
        pool_data=jnp.asarray(method == "centralized"),
        use_coord=jnp.asarray(method == "bso-sl"),
        base_assign=singleton_assignments(n_clients) if method == "local"
        else zeros)


def make_sweep_config(n_clients: int,
                      methods=SWEEP_METHODS) -> MethodParams:
    """Stacked :class:`MethodParams` with a leading (M,) method axis —
    the ``SweepConfig`` that :func:`run_sweep` vmaps over."""
    rows = [method_params(m, n_clients) for m in methods]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *rows)


class ChurnParams(NamedTuple):
    """Traced per-round churn knobs — the scenario axis as engine data.

    Real fleets have clients that drop, lag and rejoin; this axis makes
    "how robust is BSO-SL at 30% dropout?" traced data on the same one
    compiled program, exactly the :class:`MethodParams` move:

    * an ABSENT client skips the local phase (masked no-op — keys are
      consumed unconditionally so every churn row shares one program),
      keeps its stale params (it never receives the round's Eq. 2
      aggregate), contributes zero — or a staleness-decayed echo — of
      weight to its cluster's Eq. 2 sum, and is excluded from the
      k-means stats matrix (see :mod:`repro.core.kmeans` masks; an
      all-absent cluster rides the existing empty-cluster reseed).
    * ``stale_decay`` = λ selects the aggregation semantics: the
      effective Eq. 2 weight of client h is ``|D_h| * λ^staleness``
      where ``staleness`` counts rounds since last participation
      (carried in :attr:`SwarmState.staleness`, reset to 0 on
      participation). λ=0 is the plain hard mask (``0^0 = 1`` keeps
      every present client at full weight), λ→1 lets stale params
      linger in the aggregate at decaying weight.

    ``dropout = 0.0`` (with no explicit mask) draws an all-ones mask,
    which is BITWISE the no-churn engine path — the parity anchor
    ``tests/test_churn.py`` pins.
    """
    dropout: Any          # () float32 — per-round P(client absent);
                          #   the Bernoulli draw rides a fold_in of the
                          #   round's sampling key (stream-disjoint)
    stale_decay: Any      # () float32 λ — Eq. 2 staleness weight decay
                          #   (0 = hard mask, see above)
    mask: Any = None      # optional explicit participation mask
                          #   overriding the Bernoulli draw: (N,) for
                          #   every round, or a (rounds, N) schedule
                          #   (run_rounds scans one row per round)


def churn_params(dropout: float = 0.0, stale_decay: float = 0.0,
                 mask=None) -> ChurnParams:
    """One :class:`ChurnParams` row. ``mask`` (optional) pins the
    participation pattern explicitly — (N,) for a fixed mask, or a
    (rounds, N) schedule consumed row-per-round by :func:`run_rounds`;
    without it each round Bernoulli-drops clients at ``dropout``."""
    d = float(dropout)
    if not 0.0 <= d <= 1.0:
        raise ValueError(f"dropout={d} outside [0, 1]")
    g = float(stale_decay)
    if not 0.0 <= g <= 1.0:
        raise ValueError(f"stale_decay={g} outside [0, 1]")
    if mask is not None:
        mask = jnp.asarray(mask, bool)
        if mask.ndim not in (1, 2):
            raise ValueError("churn mask must be (N,) or (rounds, N), "
                             f"got shape {mask.shape}")
    return ChurnParams(dropout=jnp.asarray(d, jnp.float32),
                       stale_decay=jnp.asarray(g, jnp.float32),
                       mask=mask)


class GridPoint(NamedTuple):
    """Traced per-row hyper-parameters — grid axes as engine data.

    A strict superset of the method axis: ``method`` is the Table-II
    mask row (grid rows default to the full bso-sl path) and the knobs
    override the corresponding :class:`EngineConfig` statics, which act
    as the row's *pads/maxima*:

    * ``n_clusters`` ``<= cfg.n_clusters`` (the static ``k_max``) —
      k-means + brain storm run masked to the first ``n_clusters``
      slots (see :mod:`repro.core.kmeans`),
    * ``local_steps`` ``<= cfg.local_steps`` — the local phase computes
      every static step but applies only the first ``local_steps``
      (the key stream is consumed unconditionally so all rows share
      one program),
    * ``p1`` / ``p2`` / ``lr`` — pure value overrides.

    Build rows with :func:`grid_point`, stack them with
    :func:`make_grid_config`, and :func:`run_grid` vmaps the fit over
    the stack.
    """
    method: MethodParams  # Table-II masks (pool_data/use_coord/base_assign)
    n_clusters: Any       # () int32 active cluster count, 1..cfg.n_clusters
    p1: Any               # () float32 center-replacement threshold
    p2: Any               # () float32 center-swap threshold
    local_steps: Any      # () int32 applied local steps, 1..cfg.local_steps
    lr: Any               # () float32 local-phase learning rate
    churn: Any = None     # ChurnParams scenario row, or None (no churn)


def grid_point(cfg: "EngineConfig", n_clients: int, *, method: str = "bso-sl",
               k=None, p1=None, p2=None, local_steps=None, lr=None,
               dropout=None, stale_decay=None, churn_mask=None) -> GridPoint:
    """One :class:`GridPoint` from a spec; ``None`` knobs inherit the
    engine-config value (so the empty spec is exactly the paper point).
    ``k``/``local_steps`` are validated against the static maxima at
    build time — the traced program only sees in-range values.

    ``dropout`` / ``stale_decay`` / ``churn_mask`` build a
    :class:`ChurnParams` scenario row (any of them given opts the row
    in; ``dropout=0.0`` is the bitwise no-churn anchor). Grid rows must
    be uniformly churn or churn-free — :func:`make_grid_config` checks.
    """
    k = cfg.n_clusters if k is None else int(k)
    if not 1 <= k <= cfg.n_clusters:
        raise ValueError(f"grid k={k} outside [1, {cfg.n_clusters}] — "
                         f"cfg.n_clusters is the static pad k_max")
    steps = cfg.local_steps if local_steps is None else int(local_steps)
    if not 1 <= steps <= cfg.local_steps:
        raise ValueError(f"grid local_steps={steps} outside "
                         f"[1, {cfg.local_steps}] — cfg.local_steps is "
                         f"the static step budget")
    churn = None
    if dropout is not None or stale_decay is not None \
            or churn_mask is not None:
        churn = churn_params(0.0 if dropout is None else dropout,
                             0.0 if stale_decay is None else stale_decay,
                             churn_mask)
    return GridPoint(
        method=method_params(method, n_clients),
        n_clusters=jnp.asarray(k, jnp.int32),
        p1=jnp.asarray(cfg.p1 if p1 is None else p1, jnp.float32),
        p2=jnp.asarray(cfg.p2 if p2 is None else p2, jnp.float32),
        local_steps=jnp.asarray(steps, jnp.int32),
        lr=jnp.asarray(cfg.lr if lr is None else lr, jnp.float32),
        churn=churn)


def grid_axes(**axes) -> list:
    """Cartesian product of named axes into grid-point specs::

        grid_axes(k=(1, 2, 3), p1=(0.9, 1.0))
        # -> [{'k': 1, 'p1': 0.9}, {'k': 1, 'p1': 1.0}, ...]

    Axis names are :func:`grid_point` keywords (``k``, ``p1``, ``p2``,
    ``local_steps``, ``lr``, ``method``, and the churn axes
    ``dropout`` / ``stale_decay`` / ``churn_mask``). Point order is
    row-major in the given axis order — the row order of
    :func:`make_grid_config`.
    """
    names = list(axes)
    return [dict(zip(names, combo))
            for combo in itertools.product(*(axes[n] for n in names))]


def make_grid_config(cfg: "EngineConfig", n_clients: int,
                     specs: Sequence[dict]) -> GridPoint:
    """Stacked :class:`GridPoint` with a leading (G,) grid axis — the
    grid that :func:`run_grid` vmaps over. ``specs`` is a list of
    :func:`grid_point` keyword dicts (see :func:`grid_axes`)."""
    rows = [grid_point(cfg, n_clients, **s) for s in specs]
    has_churn = [r.churn is not None for r in rows]
    if any(has_churn) and not all(has_churn):
        raise ValueError(
            "grid rows must be uniformly churn or churn-free (stacking "
            "mixes pytree structures); give the always-on rows "
            "dropout=0.0 — it is the bitwise no-churn anchor")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *rows)


@dataclass(frozen=True)
class HierParams:
    """Static two-tier coordination topology — the million-client axis.

    The flat coordinator is O(N) in clients: every round it clusters
    the full (N, 2*#tensors) stats matrix and brain-storms over N
    assignments. ``HierParams`` shards the swarm into *pods* that each
    run a local k-means over their own members' stats, and the global
    tier (k-means + brain storm) runs over the ``n_pods * k_local``
    pod-cluster summaries instead — centroids weighted by member
    counts (the :func:`repro.core.kmeans.kmeans` ``weights`` axis), a
    pod-cluster's val score the mean of its members'. A client's
    global cluster is the composition ``g[pod * k_local + a_local]``;
    Eq. 2 aggregation is unchanged (N-segment ``cluster_fedavg``), so
    only the *coordinator* shrinks from O(clients) to O(pods).

    Pod membership is STATIC (tuples — this dataclass is a jit static
    argument like :class:`EngineConfig`): the topology shapes the
    program, exactly as bucket membership does in
    :class:`BucketedSwarmData`. Unequal pods are fine in the sim
    engine; the fleet surface wants equal contiguous pods (one per
    mesh shard — see :func:`make_fleet_round`).

    ``hier=None`` everywhere is the flat path untouched; a single-pod
    ``HierParams`` routes to the flat coordinator *verbatim* (one pod
    means the pod-local clustering IS the global clustering, so the
    two-tier math degenerates — the engine short-circuits statically
    and ``tests/test_hier.py`` pins bitwise equality).
    """
    pods: tuple          # tuple[tuple[int, ...], ...] — partition of
    #                      range(N), pod p's member client ids
    k_local: int = 2     # per-pod local cluster count

    @property
    def n_pods(self) -> int:
        return len(self.pods)


def hier_params(n_clients: int, n_pods: int, k_local: int = 2,
                pods=None) -> HierParams:
    """Build a validated :class:`HierParams`. Default topology is
    ``n_pods`` contiguous near-equal pods; pass explicit ``pods``
    (iterable of member-id iterables) for arbitrary membership.
    ``k_local`` must fit the smallest pod."""
    if pods is None:
        if not 1 <= n_pods <= n_clients:
            raise ValueError(f"n_pods={n_pods} outside [1, {n_clients}]")
        bounds = np.linspace(0, n_clients, n_pods + 1).astype(int)
        pods = tuple(tuple(range(int(a), int(b)))
                     for a, b in zip(bounds[:-1], bounds[1:]))
    else:
        pods = tuple(tuple(int(i) for i in p) for p in pods)
    seen = sorted(i for p in pods for i in p)
    if seen != list(range(n_clients)):
        raise ValueError("pods must partition range(n_clients) — got "
                         f"{len(seen)} member ids for N={n_clients}")
    smallest = min(len(p) for p in pods)
    if not 1 <= int(k_local) <= smallest:
        raise ValueError(f"k_local={k_local} outside [1, {smallest}] "
                         "(the smallest pod bounds the local cluster "
                         "count)")
    return HierParams(pods=pods, k_local=int(k_local))


@dataclass(frozen=True)
class EngineConfig:
    """Static round configuration (hashable — a jit static argument).

    Holds the model/optimizer *objects*: both are frozen dataclasses of
    pure functions, so configs built from the same instances hash equal
    and share the compiled round program.
    """
    model: Model
    opt: Optimizer
    local_steps: int
    batch_size: int
    lr: float
    aggregation: str = "bso"         # bso | fedavg | none
    n_clusters: int = 3
    p1: float = 0.9
    p2: float = 0.8
    kmeans_iters: int = 20
    use_pallas: bool = False
    reset_opt_each_round: bool = False
    local_unroll: int = 1            # scan unroll of the local phase
                                     # (CPU wants local_steps, TPU 1)


def resolve_local_steps(swarm: SwarmConfig, clients_data,
                        batch_size: int) -> int:
    """The per-round local step count: explicit ``swarm.local_steps``,
    else ``local_epochs`` over the mean clinic size — ONE copy of the
    rule, shared by SwarmTrainer and the baselines' engine slices so
    the two can never silently diverge."""
    if swarm.local_steps is not None:
        return swarm.local_steps
    mean_n = float(np.mean([c["n_train"] for c in clients_data]))
    return max(1, swarm.local_epochs * int(np.ceil(mean_n / batch_size)))


# --------------------------------------------------------------- data layout


def make_batch(cfg: ModelConfig, X, y):
    if cfg.family == "cnn":
        return {"images": jnp.asarray(X), "labels": jnp.asarray(y)}
    return {"tokens": jnp.asarray(X), "labels": jnp.asarray(y)}


def pad_eval_split(X, y, n_to: int):
    """Pad an eval slice to ``n_to`` rows: zero inputs, label=-1 rows
    (the loss/accuracy mask) — the one copy of the masking convention
    shared by the per-client loop and the stacked vmapped eval."""
    pad = n_to - len(y)
    if pad:
        X = np.concatenate([X, np.zeros((pad,) + X.shape[1:], X.dtype)])
        y = np.concatenate([y, -np.ones((pad,) + y.shape[1:], y.dtype)])
    return X, y


def stack_eval_split(cfg: ModelConfig, clients_data, split: str,
                     batch: int = 64):
    """Client-stacked eval data for one split, shaped
    (N, n_batches, batch, ...): every client padded to the largest
    client rounded up to the microbatch size, pad rows label=-1
    (masked)."""
    n_max = max(len(c[split][1]) for c in clients_data)
    n_to = -(-n_max // batch) * batch
    Xs, ys = [], []
    for c in clients_data:
        X, y = pad_eval_split(*c[split], n_to)
        Xs.append(X.reshape((n_to // batch, batch) + X.shape[1:]))
        ys.append(y.reshape((n_to // batch, batch) + y.shape[1:]))
    return make_batch(cfg, np.stack(Xs), np.stack(ys))


def make_swarm_data(cfg: ModelConfig, clients_data, *,
                    eval_batch: int = 64) -> SwarmData:
    """Build the device-resident :class:`SwarmData` from the per-clinic
    host dicts. Train sets are padded to the largest client with
    label=-1 poison rows; ``train_n`` bounds the on-device sampler so
    pads are never drawn. Val splits are grouped by the number of
    ``eval_batch``-row microbatches each needs (groups in ascending
    count), each group stacked by :func:`stack_eval_split`."""
    n_max = max(len(c["train"][1]) for c in clients_data)
    Xs, ys = [], []
    for c in clients_data:
        X, y = pad_eval_split(*c["train"], n_max)
        Xs.append(X)
        ys.append(y)
    train = make_batch(cfg, np.stack(Xs), np.stack(ys))
    train_n = jnp.asarray([len(c["train"][1]) for c in clients_data],
                          jnp.int32)
    n_batches = [-(-len(c["val"][1]) // eval_batch) for c in clients_data]
    val_ids = [tuple(i for i, n in enumerate(n_batches) if n == m)
               for m in sorted(set(n_batches))]
    val = [stack_eval_split(cfg, [clients_data[i] for i in ids], "val",
                            batch=eval_batch) for ids in val_ids]
    return SwarmData(train=train, train_n=train_n, val=val, val_ids=val_ids)


def make_bucketed_swarm_data(cfg: ModelConfig, clients_data, *,
                             eval_batch: int = 64, max_buckets: int = 4,
                             strategy: str = "pow2") -> BucketedSwarmData:
    """Build the ragged :class:`BucketedSwarmData` from the per-clinic
    host dicts: clients grouped into size buckets by their train-split
    size (:func:`repro.data.dr.bucket_clients`), each bucket's train
    stack padded only to the bucket's largest client and its eval stack
    built by :func:`stack_eval_split` over the bucket's members (so the
    eval pad also shrinks to the bucket ceiling). ``train_n`` stays in
    global client order — the sampler contract of :class:`SwarmData`.
    """
    from repro.data.dr import bucket_clients
    sizes = [len(c["train"][1]) for c in clients_data]
    groups = bucket_clients(sizes, max_buckets=max_buckets,
                            strategy=strategy)
    trains, vals = [], []
    for ids in groups:
        subset = [clients_data[i] for i in ids]
        n_max = max(len(c["train"][1]) for c in subset)
        Xs, ys = [], []
        for c in subset:
            X, y = pad_eval_split(*c["train"], n_max)
            Xs.append(X)
            ys.append(y)
        trains.append(make_batch(cfg, np.stack(Xs), np.stack(ys)))
        vals.append(stack_eval_split(cfg, subset, "val", batch=eval_batch))
    train_n = jnp.asarray(sizes, jnp.int32)
    return BucketedSwarmData(train=trains, val=vals, train_n=train_n,
                             client_ids=groups)


def pad_fraction(data) -> dict:
    """Host-side pad accounting for either layout: the fraction of
    stored train/eval rows that are padding — the waste metric
    ``BENCH_bucket.json`` quantifies. Returns ``{"train": f, "eval": f,
    "total": f, "stored_rows": n, "real_rows": n}``."""
    trains = (data.train if isinstance(data, BucketedSwarmData)
              else (data.train,))
    tr_stored = sum(int(np.prod(jax.tree.leaves(t)[0].shape[:2]))
                    for t in trains)
    tr_real = int(np.sum(np.asarray(data.train_n)))
    ev_stored = ev_real = 0
    for v in data.val:
        labels = np.asarray(v["labels"])
        ev_stored += labels.size
        ev_real += int((labels >= 0).sum())
    stored = tr_stored + ev_stored
    real = tr_real + ev_real
    return {"train": 1.0 - tr_real / tr_stored,
            "eval": 1.0 - ev_real / ev_stored,
            "total": 1.0 - real / stored,
            "stored_rows": stored, "real_rows": real}


def make_swarm_state(model: Model, opt: Optimizer, clients_data,
                     key) -> SwarmState:
    """Fresh per-client params/opt state + the round-driving key."""
    init_key, round_key = jax.random.split(key)
    keys = jax.random.split(init_key, len(clients_data))
    params = jax.vmap(model.init)(keys)
    opt_state = jax.vmap(opt.init)(params)
    n_samples = jnp.asarray([c["n_train"] for c in clients_data],
                            jnp.float32)
    return SwarmState(params=params, opt_state=opt_state, key=round_key,
                      round=jnp.zeros((), jnp.int32), n_samples=n_samples,
                      staleness=jnp.zeros((len(clients_data),), jnp.int32))


def make_sweep_state(model: Model, opt: Optimizer, clients_data,
                     keys) -> SwarmState:
    """Method-stacked :class:`SwarmState`: row m is exactly the state
    :func:`make_swarm_state` builds from ``keys[m]``, so a sweep row
    and a serial :func:`run_rounds` call seeded with the same key share
    one PRNG chain (the parity property ``tests/test_sweep.py`` pins).
    """
    states = [make_swarm_state(model, opt, clients_data, k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def make_grid_state(model: Model, opt: Optimizer, clients_data,
                    keys) -> SwarmState:
    """Grid-stacked :class:`SwarmState`: row g is exactly the state
    :func:`make_swarm_state` builds from ``keys[g]`` — the same
    stacking contract as :func:`make_sweep_state`, so a grid row and a
    serial :func:`run_rounds` call seeded with the same key share one
    PRNG chain (the parity property ``tests/test_grid.py`` pins)."""
    return make_sweep_state(model, opt, clients_data, keys)


# -------------------------------------------------------------- round pieces


def sample_local_batch(key, train, train_n, batch_size: int):
    """On-device per-client minibatch: uniform-with-replacement indices
    bounded per client by ``train_n`` (pad rows are unreachable), then a
    vmapped gather — no host loop, no data transfer."""
    N = train_n.shape[0]
    idx = jax.random.randint(key, (N, batch_size), 0, train_n[:, None])
    return jax.tree.map(
        lambda x: jax.vmap(lambda a, i: a[i])(x, idx), train)


def _swarm_batch_indices(key, train_n, batch_size: int, pool):
    """The ONE copy of the method-axis index math: (client, row) pairs
    for one stacked minibatch, layout-independent (both the rectangular
    and the bucketed gathers consume these, so their batches are
    bitwise equal).

    * pool off — the exact draw :func:`sample_local_batch` makes (same
      key, same randint call), so non-centralized sweep rows sample
      bitwise-identical batches to the plain engine path.
    * pool on — every client's slot draws a uniform *global* row id in
      [0, sum(train_n)) (a fold_in'd key keeps the stream disjoint) and
      maps it to (client, row) via the cumulative client sizes: the
      centralized method's "merged client", N replicas wide. Pad rows
      stay unreachable in both branches.
    """
    N = train_n.shape[0]
    own_row = jax.random.randint(key, (N, batch_size), 0, train_n[:, None])
    own_client = jnp.broadcast_to(
        jnp.arange(N, dtype=jnp.int32)[:, None], (N, batch_size))
    cum = jnp.cumsum(train_n)
    g = jax.random.randint(jax.random.fold_in(key, 1), (N, batch_size),
                           0, cum[-1])
    pool_client = jnp.searchsorted(cum, g, side="right").astype(jnp.int32)
    pool_row = g - (cum[pool_client] - train_n[pool_client])
    client = jnp.where(pool, pool_client, own_client)
    row = jnp.where(pool, pool_row, own_row)
    return client, row


def sample_swarm_batch(key, train, train_n, batch_size: int, pool):
    """Method-axis minibatch sampler over the rectangular stack:
    ``pool`` (a traced () bool) selects between the per-client draw and
    the pooled-global draw inside one program (see
    :func:`_swarm_batch_indices`)."""
    client, row = _swarm_batch_indices(key, train_n, batch_size, pool)
    return jax.tree.map(lambda x: x[client, row], train)


def _bucket_maps(client_ids, n_clients: int):
    """Static (bucket, position) lookup per client id — host numpy, so
    bucketed gathers trace to fixed-shape ops."""
    bucket_of = np.zeros(n_clients, np.int32)
    pos_of = np.zeros(n_clients, np.int32)
    for b, ids in enumerate(client_ids):
        for p, c in enumerate(ids):
            bucket_of[c] = b
            pos_of[c] = p
    return bucket_of, pos_of


def _gather_bucketed_rows(data: BucketedSwarmData, client, row):
    """``train[client, row]`` over the bucketed stacks — per-bucket
    gathers select-merged by static bucket membership, so the values
    are bitwise the rectangular gather's (every (client, row) pair maps
    to its bucket's (position, row) slot; out-of-bucket lanes gather a
    safe dummy and are masked out)."""
    N = data.train_n.shape[0]
    bucket_of, pos_of = _bucket_maps(data.client_ids, N)
    b_of = jnp.asarray(bucket_of)[client]
    pos = jnp.asarray(pos_of)[client]
    out = None
    for b, tr in enumerate(data.train):
        in_b = b_of == b
        p = jnp.where(in_b, pos, 0)
        r = jnp.where(in_b, row, 0)
        g = jax.tree.map(lambda x: x[p, r], tr)
        if out is None:
            out = g
        else:
            def sel(new, old):
                m = in_b.reshape(in_b.shape + (1,) * (new.ndim
                                                      - in_b.ndim))
                return jnp.where(m, new, old)
            out = jax.tree.map(sel, g, out)
    return out


def _sample_local_bucketed(key, data: BucketedSwarmData, batch_size: int):
    """Bucketed :func:`sample_local_batch`: the IDENTICAL global index
    draw (same key, same (N, batch) randint over the global-order
    ``train_n`` bounds), gathered per bucket and restored to original
    client order — bitwise the rectangular batch, at bucket-local
    storage cost."""
    N = data.train_n.shape[0]
    idx = jax.random.randint(key, (N, batch_size), 0,
                             data.train_n[:, None])
    parts = []
    for ids, tr in zip(data.client_ids, data.train):
        ids_arr = np.asarray(ids)
        parts.append(jax.tree.map(
            lambda x: jax.vmap(lambda a, i: a[i])(x, idx[ids_arr]), tr))
    cat = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)
    perm = np.concatenate([np.asarray(ids) for ids in data.client_ids])
    inv = np.argsort(perm)
    return jax.tree.map(lambda x: x[inv], cat)


def sample_round_batch(key, data, batch_size: int, pool=None):
    """Layout-dispatching per-step minibatch: the one sampler surface
    :func:`swarm_round` (and the scheduled grid path) calls. ``data``
    is a :class:`SwarmData` or :class:`BucketedSwarmData`; ``pool`` is
    the traced method-axis pooling flag (None = the plain per-client
    path). Both layouts consume the same index draws, so the returned
    batches are bitwise identical."""
    if isinstance(data, BucketedSwarmData):
        if pool is None:
            return _sample_local_bucketed(key, data, batch_size)
        client, row = _swarm_batch_indices(key, data.train_n, batch_size,
                                           pool)
        return _gather_bucketed_rows(data, client, row)
    if pool is None:
        return sample_local_batch(key, data.train, data.train_n,
                                  batch_size)
    return sample_swarm_batch(key, data.train, data.train_n, batch_size,
                              pool)


def local_phase(step, params, opt_state, lr, xs, batch_for_step, *,
                unroll: int = 1, n_active=None, present=None):
    """The shared local-training body of both regimes: a scan of
    vmapped train steps over the client axis.

    ``xs`` is the scan input (sim: per-step sample keys; fleet: step
    indices) and ``batch_for_step(x)`` materialises that step's stacked
    (N, B, ...) batch — sampling a fresh gather in the sim regime,
    slicing the uploaded round batch in the fleet regime.

    ``n_active`` (a traced () int32, or None) is the grid engine's
    local-step override: every static step still computes (fixed
    shapes, unconditional key consumption — all grid rows share one
    program) but steps ``>= n_active`` leave params/opt state
    untouched, so applying all steps is bitwise the plain path.

    ``present`` (a traced (N,) participation mask, or None) is the
    churn axis's local-phase gate: every client still computes every
    step (fixed shapes, unconditional key consumption — all churn
    schedules share one program) but absent clients' params/opt state
    are where-selected back, a per-client masked no-op, and the step
    loss averages over present clients only. All-ones is bitwise the
    unmasked path (``where(True, ...)`` identity; the masked loss mean
    reduces over the identical addends).

    ``unroll`` trades compile time for loop overhead: XLA's CPU backend
    executes ops inside a while body markedly slower than the same ops
    unrolled (~2x on convs), so CPU benchmarking wants
    ``unroll=len(xs)``; TPU and large models want the rolled default."""
    vstep = jax.vmap(step, in_axes=(0, 0, 0, None))
    if present is not None:
        present = jnp.asarray(present, bool)

        def sel_client(new, old):
            m = present.reshape(present.shape
                                + (1,) * (new.ndim - present.ndim))
            return jnp.where(m, new, old)

    def body(carry, ix):
        i, x = ix
        p, o = carry
        p2, o2, m = vstep(p, o, batch_for_step(x), lr)
        if n_active is not None:
            on = i < n_active
            p2 = jax.tree.map(lambda new, old: jnp.where(on, new, old),
                              p2, p)
            o2 = jax.tree.map(lambda new, old: jnp.where(on, new, old),
                              o2, o)
        if present is None:
            loss = jnp.mean(m["loss"])
        else:
            p2 = jax.tree.map(sel_client, p2, p)
            o2 = jax.tree.map(sel_client, o2, o)
            pf = present.astype(jnp.float32)
            # reciprocal-multiply, not divide: XLA strength-reduces
            # jnp.mean's constant denominator to a reciprocal multiply,
            # so the all-ones masked mean is only bitwise-equal to
            # jnp.mean if it rounds through the same reciprocal
            loss = (jnp.sum(m["loss"] * pf)
                    * (1.0 / jnp.maximum(jnp.sum(pf), 1.0)))
        return (p2, o2), loss

    n_steps = jax.tree.leaves(xs)[0].shape[0]
    with jax.named_scope("bso.local_phase"):
        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), (jnp.arange(n_steps), xs), unroll=unroll)
    return params, opt_state, losses


def make_client_eval(model: Model):
    """Per-client masked accuracy over stacked (N, n_batches, batch, ..)
    eval data — one vmapped program, scanning fixed microbatches so the
    activation footprint stays O(N * batch) regardless of split size."""
    eval_step = make_eval_step(model)

    def client_eval(params, batches):
        def one(carry, bt):
            hits, tot = carry
            m = eval_step(params, bt)
            valid = jnp.sum(bt["labels"] >= 0).astype(jnp.float32)
            return (hits + m["acc"] * valid, tot + valid), None

        (hits, tot), _ = jax.lax.scan(
            one, (jnp.float32(0.0), jnp.float32(0.0)), batches)
        return hits / jnp.maximum(tot, 1.0)

    return jax.vmap(client_eval)


def eval_swarm(model: Model, params, data):
    """Per-client val accuracy — the masked segment reduction over the
    val groups of either layout (:attr:`SwarmData.val_ids`, the buckets
    of :class:`BucketedSwarmData`).

    One fixed-shape vmapped :func:`make_client_eval` program per group
    (same static-shape discipline as :func:`run_grid` — equal group
    signatures share the trace), client accuracies scattered back to
    global order; a single ``range(N)`` group is evaluated as it
    stands, with no gather or scatter. BITWISE the one rectangular
    stack's result: a group's stack is a microbatch-prefix of the
    rectangular stack, and every dropped all-pad microbatch contributed
    exactly +0.0 to the (hits, total) accumulator (``accuracy`` masks
    label=-1 rows and divides by ``max(valid, 1)``).
    """
    with jax.named_scope("bso.eval"):
        ev = make_client_eval(model)
        N = data.train_n.shape[0]
        if data.val_ids == (tuple(range(N)),):
            return ev(params, data.val[0])
        acc = jnp.zeros((N,), jnp.float32)
        for ids, val_g in zip(data.val_ids, data.val):
            ids_arr = np.asarray(ids)
            sub = jax.tree.map(lambda x: x[ids_arr], params)
            acc = acc.at[ids_arr].set(ev(sub, val_g))
        return acc


# ---------------------------------------------------------------- the round


def _coordinate_and_aggregate(params, opt_state, val, n_samples,
                              cfg: "EngineConfig", masks: MethodParams,
                              grid, k_kmeans, k_bso, present=None,
                              eff_w=None):
    """The method/grid-axis coordinator + Eq. 2 tail of
    :func:`swarm_round`, factored out so the sorted-schedule grid path
    can vmap exactly the same ops over its rows: distribution stats →
    masked k-means → brain storm → traced-mask selection → N-segment
    ``cluster_fedavg``. Returns ``(params, opt_state, assignments,
    centers, n_replaced, n_swapped)``.

    ``present`` / ``eff_w`` (both None, or both set) are the churn
    axis: absent clients are masked out of the k-means stats matrix
    (an all-absent cluster rides its empty reseed), their brain-storm
    scores are the recomputed scores of their stale params (the
    deterministic equivalent of a server-cached last report), and
    Eq. 2 runs the masked variant — effective weights ``eff_w``
    (zero or staleness-decayed for absent clients), aggregates
    delivered to present clients only."""
    N = n_samples.shape[0]
    zero = jnp.zeros((), jnp.int32)
    # the method/grid axis: one program, per-row traced masks. The
    # aggregation segment count is N so every base_assign plan
    # (arange = identity, zeros = global) shares the bso layout.
    # cfg.n_clusters is the static pad k_max; a grid row masks the
    # coordinator down to its traced point.n_clusters.
    k = cfg.n_clusters
    assert k <= N, "method axis needs n_clusters <= n_clients"
    k_act = None if grid is None else grid.n_clusters
    p1 = cfg.p1 if grid is None else grid.p1
    p2 = cfg.p2 if grid is None else grid.p2
    feats = swarm_distribution_matrix(params, use_pallas=cfg.use_pallas)
    _, a0 = kmeans(k_kmeans, feats, k=k, iters=cfg.kmeans_iters,
                   use_pallas=cfg.use_pallas, k_active=k_act,
                   mask=present)
    bsa_a, bsa_c, n_rep, n_swap = brain_storm_jax(
        k_bso, a0, val, k, p1, p2)
    use = masks.use_coord
    assignments = jnp.where(use, bsa_a, masks.base_assign)
    centers = jnp.where(use, bsa_c, -1)
    n_rep = jnp.where(use, n_rep, zero)
    n_swap = jnp.where(use, n_swap, zero)
    if present is None:
        params = cluster_fedavg(params, assignments, n_samples, k=N)
    else:
        params = cluster_fedavg_masked(params, assignments, eff_w,
                                       present, k=N)
    if cfg.reset_opt_each_round:
        new_opt = jax.vmap(cfg.opt.init)(params)
        if present is None:
            opt_state = new_opt
        else:
            def sel(new, old):
                m = present.reshape(present.shape
                                    + (1,) * (new.ndim - 1))
                return jnp.where(m, new, old)
            opt_state = jax.tree.map(sel, new_opt, opt_state)
    return params, opt_state, assignments, centers, n_rep, n_swap


def pod_summaries(feats, val, weights, present, k_local: int,
                  kmeans_iters: int, key, pods, *,
                  use_pallas: bool = False):
    """The pod tier of the hierarchical coordinator: per-pod local
    k-means over member stats, reduced to O(pods * k_local) summaries.

    ``pods`` is the static membership (tuple of member-id tuples —
    :attr:`HierParams.pods`); the loop over pods is a static python
    loop, so unequal pods trace to their own fixed shapes inside the
    ONE program. Pod ``p`` clusters its members' ``feats`` rows with
    key ``fold_in(key, p)`` (mask = the members' ``present`` slice, so
    churn composes exactly as in the flat path), then segment-sums its
    members into per-pod-cluster summaries.

    Returns ``(centroids (P*kl, F), counts (P*kl,), wsums (P*kl,),
    valsums (P*kl,), pc_of (N,))`` where ``counts`` are *present*
    member counts, ``wsums`` sum the members' effective Eq. 2 weights
    (``weights``), ``valsums`` their val scores, and ``pc_of`` maps
    each client to its global pod-cluster row ``p * k_local + a_local``
    (absent clients included — their membership feeds the
    staleness-weighted Eq. 2, mirroring the masked flat k-means).

    This is exactly the payload the fleet surface uploads to the host
    coordinator — the O(pods) traffic claim of ``BENCH_hier.json``.
    """
    N = val.shape[0]
    kl = int(k_local)
    cents, cnts, wss, vss = [], [], [], []
    pc_of = jnp.zeros((N,), jnp.int32)
    for p, ids in enumerate(pods):
        idx = np.asarray(ids)
        f_p = feats[idx]
        m_p = None if present is None else present[idx]
        C_p, a_p = kmeans(jax.random.fold_in(key, p), f_p, k=kl,
                          iters=kmeans_iters, use_pallas=use_pallas,
                          mask=m_p)
        w_p = (jnp.ones((len(ids),), feats.dtype) if m_p is None
               else m_p.astype(feats.dtype))
        cents.append(C_p)
        cnts.append(jax.ops.segment_sum(w_p, a_p, kl))
        wss.append(jax.ops.segment_sum(weights[idx] * w_p, a_p, kl))
        vss.append(jax.ops.segment_sum(val[idx] * w_p, a_p, kl))
        pc_of = pc_of.at[idx].set(p * kl + a_p.astype(jnp.int32))
    return (jnp.concatenate(cents, axis=0), jnp.concatenate(cnts),
            jnp.concatenate(wss), jnp.concatenate(vss), pc_of)


def global_tier(key_kmeans, k_bso, centroids, counts, valsums, *,
                k: int, kmeans_iters: int, p1, p2,
                use_pallas: bool = False):
    """The global tier of the hierarchical coordinator, over pod
    summaries instead of clients: member-count-weighted k-means
    (the centroid-input mode of :func:`repro.core.kmeans.kmeans`) +
    ``brain_storm_jax`` ranking pod-cluster mean val scores.

    Empty pod-clusters (``counts == 0`` — a pod's k-means left a slot
    unoccupied, or every member was absent) carry zero k-means weight
    and a val score of -1.0, so they never win a best-val center and
    their occasional selection as a random replacement target moves no
    real clients (they have none) — the same inertness contract the
    flat path's pad clusters rely on. The brain storm's swap
    granularity here is a whole pod-cluster: one swap moves every
    member of the summary row, the price of ranking O(pods) rows
    instead of O(clients).

    Returns ``(g (P*kl,) pod-cluster -> global cluster, centers_s (k,)
    best-val summary rows, n_replaced, n_swapped)``.
    """
    occupied = counts > 0
    val_means = jnp.where(occupied,
                          valsums / jnp.maximum(counts, 1e-9), -1.0)
    _, g0 = kmeans(key_kmeans, centroids, k=k, iters=kmeans_iters,
                   use_pallas=use_pallas, weights=counts)
    g, centers_s, n_rep, n_swap = brain_storm_jax(k_bso, g0, val_means,
                                                  k, p1, p2)
    return g, centers_s, n_rep, n_swap


def _hier_coordinate_and_aggregate(params, opt_state, val, n_samples,
                                   cfg: "EngineConfig", hier: HierParams,
                                   k_kmeans, k_bso, present=None,
                                   eff_w=None):
    """The two-tier coordinator + Eq. 2 tail of :func:`swarm_round` —
    the hierarchical sibling of :func:`_coordinate_and_aggregate`
    (plain bso path only; the method/grid axes keep the flat
    coordinator). Pod tier -> global tier -> composed client
    assignments ``g[pc_of]`` -> the unchanged N-segment Eq. 2."""
    N = n_samples.shape[0]
    k = cfg.n_clusters
    P, kl = hier.n_pods, hier.k_local
    assert k <= P * kl, (
        f"hier global tier needs n_clusters={k} <= n_pods*k_local="
        f"{P * kl} summary rows")
    feats = swarm_distribution_matrix(params, use_pallas=cfg.use_pallas)
    # disjoint key streams for the pod tier and the global tier (the
    # flat path spends k_kmeans directly; fold_in(k_pods, p) per pod)
    k_pods, k_global = jax.random.split(k_kmeans)
    w = n_samples if eff_w is None else eff_w
    centroids, counts, wsums, valsums, pc_of = pod_summaries(
        feats, val, w, present, kl, cfg.kmeans_iters, k_pods, hier.pods,
        use_pallas=cfg.use_pallas)
    g, centers_s, n_rep, n_swap = global_tier(
        k_global, k_bso, centroids, counts, valsums, k=k,
        kmeans_iters=cfg.kmeans_iters, p1=cfg.p1, p2=cfg.p2,
        use_pallas=cfg.use_pallas)
    assignments = g[pc_of]
    # RoundMetrics centers want client ids: a summary-row center maps
    # to its best-val present member (-1 when the row is empty — the
    # same "no center" convention the method axis uses)
    member = pc_of[None, :] == jnp.arange(P * kl)[:, None]   # (S, N)
    if present is not None:
        member = member & present[None, :]
    score = jnp.where(member, val[None, :], -jnp.inf)
    rep = jnp.where(member.any(axis=1),
                    jnp.argmax(score, axis=1).astype(jnp.int32), -1)
    centers = jnp.where(centers_s >= 0,
                        rep[jnp.clip(centers_s, 0, P * kl - 1)], -1)
    if present is None:
        params = cluster_fedavg(params, assignments, n_samples, k=N)
    else:
        params = cluster_fedavg_masked(params, assignments, eff_w,
                                       present, k=N)
    if cfg.reset_opt_each_round:
        new_opt = jax.vmap(cfg.opt.init)(params)
        if present is None:
            opt_state = new_opt
        else:
            def sel(new, old):
                m = present.reshape(present.shape
                                    + (1,) * (new.ndim - 1))
                return jnp.where(m, new, old)
            opt_state = jax.tree.map(sel, new_opt, opt_state)
    return params, opt_state, assignments, centers, n_rep, n_swap


#: fold_in tag deriving the churn Bernoulli key from the round's local
#: sampling key — fold_in does not consume the split stream, so the
#: no-churn key discipline (and with it bitwise parity) is untouched.
_CHURN_KEY_TAG = 0x0C


def swarm_round(state: SwarmState, data: SwarmData,
                cfg: EngineConfig, method: MethodParams = None,
                churn: ChurnParams = None, hier: HierParams = None):
    """One full BSO-SL round as a pure function — local steps, eval,
    distribution upload, k-means, brain storm, Eq. 2 aggregation.

    Jit it with ``cfg`` static (see :data:`jit_swarm_round`) and the
    entire round is one device program; scan it (:func:`run_rounds`)
    and a whole training run is one program.

    ``method`` switches the body onto a traced axis: the coordinator
    (stats + k-means + brain storm) always runs, but the traced masks
    pick which assignments aggregate and whether sampling pools — so
    the one lowered program is vmappable over stacked rows. It accepts

    * a :class:`MethodParams` — the Table-II method axis
      (:func:`run_sweep` vmaps this),
    * a :class:`GridPoint` — the hyper-parameter grid axis: the method
      masks plus traced k / p1 / p2 / local-step / lr overrides of the
      config statics (:func:`run_grid` vmaps this; the statics are the
      row maxima — see :class:`GridPoint`),
    * ``None`` — the static ``cfg.aggregation`` branches keep the
      leaner single-method programs (``none`` skips the coordinator
      entirely).

    ``churn`` threads the scenario axis (:class:`ChurnParams`) through
    any of those paths; a :class:`GridPoint` carrying a churn row is
    picked up automatically. Absent clients run masked no-op local
    steps, keep their stale params through Eq. 2, and are excluded
    from the k-means stats; their staleness counters
    (:attr:`SwarmState.staleness`) increment, and participation resets
    them to 0. An all-ones mask (or ``dropout=0``) is bitwise the
    churn-free round — the parity anchor ``tests/test_churn.py`` pins.

    ``hier`` (a STATIC :class:`HierParams`, or None) switches the
    coordinator onto the two-tier path: per-pod local k-means over
    member stats, a member-count-weighted global k-means + brain storm
    over the O(pods * k_local) pod-cluster summaries, composed client
    assignments ``g[pod * k_local + a_local]``, Eq. 2 unchanged. Plain
    bso path only (the method/grid axes keep the flat coordinator —
    their masks select *against* the flat assignments); composes with
    ``churn`` (absent clients are masked out of their pod's k-means
    and carry staleness-decayed Eq. 2 weight, as in the flat path).
    ``hier=None`` is the flat path untouched and a single-pod
    ``HierParams`` routes to the flat coordinator verbatim (see
    :class:`HierParams`) — both bitwise, ``tests/test_hier.py`` pins.
    """
    model, opt = cfg.model, cfg.opt
    step = make_train_step(model, opt)
    next_key, k_local, k_kmeans, k_bso = jax.random.split(state.key, 4)

    grid = method if isinstance(method, GridPoint) else None
    masks = grid.method if grid is not None else method
    lr = cfg.lr if grid is None else grid.lr
    if churn is None and grid is not None:
        churn = grid.churn
    if hier is not None:
        if masks is not None:
            raise ValueError(
                "hier composes with the plain path only — the "
                "method/grid axes mask against the flat coordinator's "
                "assignments; run hierarchical rows as separate "
                "run_rounds fits")
        if cfg.aggregation != "bso":
            raise ValueError(
                f"hier needs cfg.aggregation='bso' (got "
                f"{cfg.aggregation!r}) — fedavg/none have no "
                "coordinator to shard")
        if hier.n_pods == 1:
            # one pod = the whole swarm: the pod-local clustering IS
            # the global clustering, so the flat coordinator is the
            # degenerate two-tier program — route to it verbatim
            # (bitwise, pinned in tests/test_hier.py)
            hier = None

    # --- churn axis: this round's participation mask + staleness
    N = data.train_n.shape[0]
    present = eff_w = staleness = None
    if churn is not None:
        if state.staleness is None:
            raise ValueError(
                "the churn axis needs SwarmState.staleness — rebuild "
                "the state with make_swarm_state (or _replace a zeros "
                "(N,) int32 field onto a pre-churn state)")
        if churn.mask is not None:
            present = jnp.asarray(churn.mask, bool)
            if present.ndim != 1:
                raise ValueError(
                    "swarm_round wants a per-round (N,) churn mask; "
                    "run_rounds scans (rounds, N) schedules")
        else:
            u = jax.random.uniform(
                jax.random.fold_in(k_local, _CHURN_KEY_TAG), (N,))
            present = u >= churn.dropout
        staleness = jnp.where(present, 0, state.staleness + 1)
        # effective Eq. 2 weight |D_h| * decay^staleness: present
        # clients multiply by decay^0 == 1.0 (bitwise |D_h|), hard
        # masking (decay=0) zeroes every absent client (0^k == 0, k>0)
        eff_w = state.n_samples * jnp.power(
            churn.stale_decay, staleness.astype(jnp.float32))

    # --- local phase: cfg.local_steps of on-device-sampled SGD (grid
    # rows apply only the first grid.local_steps of them; absent
    # churn-axis clients apply none)
    sample_keys = jax.random.split(k_local, cfg.local_steps)
    if masks is None:
        batch_for_step = lambda kt: sample_round_batch(
            kt, data, cfg.batch_size)
    else:
        batch_for_step = lambda kt: sample_round_batch(
            kt, data, cfg.batch_size, masks.pool_data)
    params, opt_state, losses = local_phase(
        step, state.params, state.opt_state, lr, sample_keys,
        batch_for_step, unroll=cfg.local_unroll,
        n_active=None if grid is None else grid.local_steps,
        present=present)
    # the last *applied* step's loss (grid rows stop early)
    train_loss = losses[-1] if grid is None else losses[grid.local_steps - 1]

    # --- eval: per-client val accuracy (shared within clusters, §III.C).
    # Absent clients are scored on their stale params — eval is
    # deterministic in (params, val split), so this IS the score the
    # coordinator cached at their last participation.
    val = eval_swarm(model, params, data)

    # --- coordinator + aggregation
    zero = jnp.zeros((), jnp.int32)
    if masks is not None:
        (params, opt_state, assignments, centers, n_rep,
         n_swap) = _coordinate_and_aggregate(
            params, opt_state, val, state.n_samples, cfg, masks, grid,
            k_kmeans, k_bso, present=present, eff_w=eff_w)
    elif cfg.aggregation == "none":
        assignments = jnp.zeros((N,), jnp.int32)
        centers = jnp.zeros((0,), jnp.int32)
        n_rep = n_swap = zero
    elif hier is not None:
        if len(hier.pods[0]) + sum(len(p) for p in hier.pods[1:]) != N:
            raise ValueError(
                f"hier pods cover {sum(len(p) for p in hier.pods)} "
                f"clients but the swarm has {N}")
        (params, opt_state, assignments, centers, n_rep,
         n_swap) = _hier_coordinate_and_aggregate(
            params, opt_state, val, state.n_samples, cfg, hier,
            k_kmeans, k_bso, present=present, eff_w=eff_w)
    else:
        if cfg.aggregation == "fedavg":
            k = 1
            assignments = jnp.zeros((N,), jnp.int32)
            centers = jnp.argmax(val)[None].astype(jnp.int32)
            n_rep = n_swap = zero
        else:
            k = cfg.n_clusters
            feats = swarm_distribution_matrix(params,
                                              use_pallas=cfg.use_pallas)
            _, a0 = kmeans(k_kmeans, feats, k=k, iters=cfg.kmeans_iters,
                           use_pallas=cfg.use_pallas, mask=present)
            assignments, centers, n_rep, n_swap = brain_storm_jax(
                k_bso, a0, val, k, cfg.p1, cfg.p2)
        if present is None:
            params = cluster_fedavg(params, assignments, state.n_samples,
                                    k=k)
        else:
            params = cluster_fedavg_masked(params, assignments, eff_w,
                                           present, k=k)
        if cfg.reset_opt_each_round:
            new_opt = jax.vmap(opt.init)(params)
            if present is None:
                opt_state = new_opt
            else:
                def sel(new, old):
                    m = present.reshape(present.shape
                                        + (1,) * (new.ndim - 1))
                    return jnp.where(m, new, old)
                opt_state = jax.tree.map(sel, new_opt, opt_state)

    new_state = SwarmState(params=params, opt_state=opt_state, key=next_key,
                           round=state.round + 1, n_samples=state.n_samples,
                           staleness=(staleness if churn is not None
                                      else state.staleness))
    metrics = RoundMetrics(mean_val_acc=jnp.mean(val), val_acc=val,
                           train_loss=train_loss, assignments=assignments,
                           centers=centers, n_replaced=n_rep,
                           n_swapped=n_swap,
                           present=(present if present is not None
                                    else jnp.ones((N,), bool)))
    return new_state, metrics


def run_rounds(state: SwarmState, data: SwarmData, cfg: EngineConfig,
               rounds: int, method: MethodParams = None,
               churn: ChurnParams = None, hier: HierParams = None):
    """Scan :func:`swarm_round` over ``rounds``: the whole multi-round
    fit as ONE device program. Metrics gain a leading (rounds,) axis.
    ``method`` threads a :class:`MethodParams` (Table-II method axis)
    or :class:`GridPoint` (hyper-parameter grid row) through every
    round; ``churn`` (or the grid row's own churn) threads the
    scenario axis — a (rounds, N) explicit mask schedule is scanned
    one row per round, everything else is closed over per round.
    ``hier`` (static) threads the two-tier coordinator topology
    through every round (see :func:`swarm_round`)."""
    if churn is None and isinstance(method, GridPoint):
        churn = method.churn
    if churn is not None and churn.mask is not None \
            and churn.mask.ndim == 2:
        if churn.mask.shape[0] != rounds:
            raise ValueError(
                f"churn mask schedule has {churn.mask.shape[0]} rows "
                f"for rounds={rounds}")

        def sched_body(s, mk):
            return swarm_round(s, data, cfg, method,
                               churn._replace(mask=mk), hier)

        return jax.lax.scan(sched_body, state, churn.mask, length=rounds)

    def body(s, _):
        return swarm_round(s, data, cfg, method, churn, hier)

    return jax.lax.scan(body, state, None, length=rounds)


def run_sweep(state: SwarmState, data: SwarmData, cfg: EngineConfig,
              sweep: MethodParams, rounds: int):
    """The whole paper-table sweep as ONE device program.

    ``state`` is method-stacked (:func:`make_sweep_state`), ``sweep``
    is the stacked :class:`MethodParams` (:func:`make_sweep_config`);
    both carry a leading (M,) axis. The single :class:`SwarmData` is
    closed over un-vmapped, so every method reads the same device
    buffers. Row m is exactly ``run_rounds(state[m], data, cfg,
    rounds, sweep[m])`` — the parity contract ``tests/test_sweep.py``
    asserts against the serial ``run_method`` slice.
    """
    def one(s, m):
        return run_rounds(s, data, cfg, rounds, m)

    return jax.vmap(one)(state, sweep)


def run_grid(state: SwarmState, data: SwarmData, cfg: EngineConfig,
             grid: GridPoint, rounds: int, schedule=None):
    """A whole hyper-parameter ablation as ONE device program.

    ``state`` is grid-stacked (:func:`make_grid_state`), ``grid`` is
    the stacked :class:`GridPoint` (:func:`make_grid_config`); both
    carry a leading (G,) axis. The single :class:`SwarmData` (or
    :class:`BucketedSwarmData`) is closed over un-vmapped, so every
    grid point reads the same device buffers — |grid| serial fits
    collapse into one vmapped executable whose static shapes come from
    the row maxima in ``cfg``. Row g is exactly ``run_rounds(state[g],
    data, cfg, rounds, grid[g])`` — the parity contract
    ``tests/test_grid.py`` asserts against the serial
    ``baselines.run_grid_point`` slice.

    ``schedule`` (a STATIC tuple of per-row applied step counts,
    mirroring each row's traced ``grid.local_steps``) switches the
    local phase onto the sorted scan schedule
    (:func:`_run_grid_scheduled`): rows with small step budgets exit
    the scan early instead of paying ``cfg.local_steps`` masked no-op
    steps. Still ONE program; parity with the masked path is allclose
    (~1 ulp — see :func:`_run_grid_scheduled`).
    """
    if schedule is not None:
        if grid.churn is not None:
            raise ValueError(
                "the sorted local-steps schedule does not support churn "
                "rows (its prefix segments assume every row trains every "
                "client); pass schedule=None — churn grids ride the "
                "masked path")
        return _run_grid_scheduled(state, data, cfg, grid, rounds,
                                   tuple(schedule))

    def one(s, g):
        return run_rounds(s, data, cfg, rounds, g)

    return jax.vmap(one)(state, grid)


def _run_grid_scheduled(state: SwarmState, data, cfg: EngineConfig,
                        grid: GridPoint, rounds: int, schedule: tuple):
    """:func:`run_grid` with a ``local_steps``-sorted scan schedule.

    The masked path pays ``G x cfg.local_steps`` train steps per round
    — rows with ``local_steps < max`` compute every step and discard
    the tail as masked no-ops (a vmap lane cannot exit a scan early).
    Here rows are pre-sorted by DESCENDING static step count and the
    local phase runs as static prefix segments: between the distinct
    step counts ``s_1 < s_2 < ...`` of the schedule, only the prefix of
    rows still inside their budget scans on (total row-steps =
    ``sum(schedule)`` instead of ``G * max``). Everything the per-row
    :func:`swarm_round` would compute is replicated — the 4-way key
    split, the per-step sample keys, the layout-dispatched sampler,
    eval, and the factored :func:`_coordinate_and_aggregate` — and a
    skipped step's masked no-op never touched params, so every applied
    step consumes identical keys and batches. Parity with the masked
    path is ALLCLOSE (~1 ulp, ``tests/test_grid.py``), not bitwise: a
    prefix segment batches the train step over ``g < G`` rows, and
    XLA's conv kernels reduce in a lane-width-dependent order — only
    rows that never leave the full-width segment match bit for bit.

    ``schedule`` must be static (it shapes the program) and must equal
    the traced per-row ``grid.local_steps`` values — the loss gather at
    ``local_steps - 1`` reads only computed slots when they agree.
    ``run_grid_table`` derives it from the row specs automatically.
    """
    G = len(schedule)
    for s in schedule:
        if not 1 <= int(s) <= cfg.local_steps:
            raise ValueError(f"schedule entry {s} outside "
                             f"[1, {cfg.local_steps}]")
    order = np.argsort(-np.asarray(schedule), kind="stable")
    inv = np.argsort(order)
    steps_sorted = [int(schedule[i]) for i in order]
    # static prefix segments: during steps [a, b), the first g rows
    # (sorted desc) are still inside their budget
    segs = []
    prev = 0
    for s in sorted(set(steps_sorted)):
        segs.append((prev, s, sum(1 for t in steps_sorted if t > prev)))
        prev = s

    state = jax.tree.map(lambda x: x[order], state)
    grid = jax.tree.map(lambda x: x[order], grid)
    model, opt = cfg.model, cfg.opt
    step = make_train_step(model, opt)
    vstep = jax.vmap(step, in_axes=(0, 0, 0, None))     # over clients
    gstep = jax.vmap(vstep, in_axes=(0, 0, 0, 0))       # over grid rows

    def round_body(st, _):
        # per-row key discipline, replicated from swarm_round exactly
        keys4 = jax.vmap(lambda kk: jax.random.split(kk, 4))(st.key)
        next_key, k_local, k_kmeans, k_bso = (keys4[:, i]
                                              for i in range(4))
        sample_keys = jax.vmap(
            lambda kk: jax.random.split(kk, cfg.local_steps))(k_local)
        params, opt_state = st.params, st.opt_state
        losses = jnp.zeros((G, cfg.local_steps), jnp.float32)

        for a, b, g in segs:
            p_g = jax.tree.map(lambda x: x[:g], params)
            o_g = jax.tree.map(lambda x: x[:g], opt_state)
            lr_g, pool_g = grid.lr[:g], grid.method.pool_data[:g]
            kts = jnp.swapaxes(sample_keys[:g, a:b], 0, 1)

            def seg_body(carry, kt, pool_g=pool_g, lr_g=lr_g):
                p, o = carry
                batch = jax.vmap(lambda kk, pl: sample_round_batch(
                    kk, data, cfg.batch_size, pl))(kt, pool_g)
                p2, o2, m = gstep(p, o, batch, lr_g)
                return (p2, o2), jnp.mean(m["loss"], axis=-1)

            (p_g, o_g), seg_losses = jax.lax.scan(
                seg_body, (p_g, o_g), kts, unroll=cfg.local_unroll)
            params = jax.tree.map(
                lambda sg, full: jnp.concatenate([sg, full[g:]], axis=0),
                p_g, params)
            opt_state = jax.tree.map(
                lambda sg, full: jnp.concatenate([sg, full[g:]], axis=0),
                o_g, opt_state)
            losses = losses.at[:g, a:b].set(jnp.swapaxes(seg_losses,
                                                         0, 1))

        train_loss = jnp.take_along_axis(
            losses, grid.local_steps[:, None] - 1, axis=1)[:, 0]
        val = jax.vmap(lambda p: eval_swarm(model, p, data))(params)
        (params, opt_state, assignments, centers, n_rep,
         n_swap) = jax.vmap(
            lambda p, o, v, ns, gg, kk, kb: _coordinate_and_aggregate(
                p, o, v, ns, cfg, gg.method, gg, kk, kb)
        )(params, opt_state, val, st.n_samples, grid, k_kmeans, k_bso)
        new_state = SwarmState(params=params, opt_state=opt_state,
                               key=next_key, round=st.round + 1,
                               n_samples=st.n_samples,
                               staleness=st.staleness)
        metrics = RoundMetrics(
            mean_val_acc=jnp.mean(val, axis=1), val_acc=val,
            train_loss=train_loss, assignments=assignments,
            centers=centers, n_replaced=n_rep, n_swapped=n_swap,
            present=jnp.ones(val.shape, bool))
        return new_state, metrics

    state, ms = jax.lax.scan(round_body, state, None, length=rounds)
    # (rounds, G, ...) -> (G, rounds, ...), then undo the sort
    ms = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1)[inv], ms)
    state = jax.tree.map(lambda x: x[inv], state)
    return state, ms


# module-level jitted entry points: the cache is shared across every
# host wrapper holding an equal EngineConfig (state buffers donated —
# each round updates the swarm in place)
jit_swarm_round = jax.jit(swarm_round, static_argnames=("cfg", "hier"),
                          donate_argnums=(0,))
jit_run_rounds = jax.jit(run_rounds,
                         static_argnames=("cfg", "rounds", "hier"),
                         donate_argnums=(0,))
jit_run_sweep = jax.jit(run_sweep, static_argnames=("cfg", "rounds"),
                        donate_argnums=(0,))
jit_run_grid = jax.jit(run_grid,
                       static_argnames=("cfg", "rounds", "schedule"),
                       donate_argnums=(0,))


# ------------------------------------------------------------- fleet regime


class FleetRoundOut(NamedTuple):
    """The tiny per-round outputs the fleet driver pulls to host.

    Everything here is O(clients): the whole device->host traffic of a
    fleet round is this pytree — the models themselves never leave the
    mesh (paper §III.B's communication-efficiency claim).
    """
    stats: Any        # (N, 2*#tensors) distribution-stat upload of the
                      #   post-local-phase params (§III.B)
    val_acc: Any      # (N,) per-client masked val accuracy — the scores
                      #   the brain-storm step ranks (§III.C step 1)
    train_loss: Any   # () mean loss of the last local step


class HierRoundOut(NamedTuple):
    """The per-round outputs of the HIERARCHICAL fleet surface.

    The flat :class:`FleetRoundOut` is O(clients); this one is O(pods):
    the round program runs each pod's local k-means on-mesh and only
    the ``S = n_pods * k_local`` pod-cluster summaries cross to the
    host (the two-tier coordinator's entire upload — ``BENCH_hier.json``
    measures exactly these arrays' bytes). ``a_local`` is (N,) but is
    NOT part of the upload: the driver feeds it back device-to-device
    as the next round's ``a_prev`` operand without ever materialising
    it on host.
    """
    centroids: Any    # (S, 2*#tensors) pod-cluster stat centroids
    counts: Any       # (S,) reporting-member counts (the global tier's
                      #   k-means weights)
    wsums: Any        # (S,) summed member Eq. 2 weights
    valsums: Any      # (S,) summed member val accuracies (mean = the
                      #   score the global brain storm ranks)
    a_local: Any      # (N,) int32 global pod-cluster index of each
                      #   client (pod * k_local + local assignment) —
                      #   device-resident feedback, never pulled
    mean_val: Any     # () swarm-mean val accuracy (all clients) — the
                      #   O(1) trajectory metric the driver logs
    train_loss: Any   # () mean loss of the last local step


def make_fleet_round(model: Model, opt: Optimizer, k: int,
                     n_local_steps: int = 1, *, use_pallas: bool = False,
                     with_eval: bool = False, with_loss: bool = False,
                     axis_name: str = None, with_churn: bool = False,
                     hier_k_local: int = 0, hier_pods: int = 0,
                     hier_kmeans_iters: int = 20):
    """Fleet round built from the same body as :func:`swarm_round`,
    reordered so a multi-round driver can close the coordinator loop
    with NO extra program: first Eq. 2 ``cluster_fedavg`` applies the
    *incoming* coordinator decision (``clusters`` computed on host from
    the previous round's stat upload; XLA SPMD inserts the cross-pod
    collectives), then the shared :func:`local_phase` runs (per-step
    microbatch slices of the uploaded round batch instead of on-device
    sampling), then the distribution-stat upload is computed *inside*
    the program — the ``param_stats_batched`` kernel under
    ``use_pallas``, the jnp oracle otherwise — so the O(#tensors) stats
    ride the same dispatch as the round step.

    Only the O(clients) coordinator decision (k-means + brain storm)
    stays host-side, matching the paper's neighbour-assignment server:
    the driver turns round r's returned ``stats`` into round r+1's
    ``clusters`` (see ``repro.launch.fleet_driver``). Seeding round 0
    with ``singleton_assignments(N)`` makes its aggregation the bitwise
    identity, so R driver rounds execute exactly the sim engine's
    protocol sequence (train -> eval -> stats -> coordinator -> Eq. 2,
    R times) with the final Eq. 2 left pending on the mesh — the
    aggregate-first rotation only moves the round boundary, not the
    order of operations.

    ``with_eval=False`` returns
    ``round_step(sparams, sopt, batch, lr, clusters, weights)
    -> (sparams, sopt, stats)`` — the dry-run lowering surface.
    ``with_eval=True`` adds the stacked eval batches argument
    (:func:`stack_eval_split` layout) and returns the full driver
    surface ``round_step(sparams, sopt, batch, val, lr, clusters,
    weights) -> (sparams, sopt, FleetRoundOut)`` — the per-client val
    accuracies are computed in-program (post-local-phase params, same
    point in the protocol as :func:`swarm_round`) because the brain
    storm ranks them.
    ``with_loss=True`` (exclusive with ``with_eval``) keeps the
    eval-free signature but returns the last-step loss alongside the
    stats — ``round_step(sparams, sopt, batch, lr, clusters, weights)
    -> (sparams, sopt, stats, loss)``. This is the bucketed-eval driver
    surface: a rectangular in-program val stack would reintroduce
    pad-to-global-max, so the driver evaluates per size bucket with its
    own fixed-shape compiled programs (one per bucket signature) and
    the round program carries only the O(1) loss out.

    ``axis_name`` switches the body onto the shard_map layout: every
    client-stacked argument is the *local* slice of a client axis split
    over that mesh axis, and Eq. 2 runs as the psum formulation
    (:func:`~repro.core.aggregation.cluster_fedavg_psum`) — the layout
    ``swarm_fleet.fleet_setup(spmd="shard_map")`` wraps, which is how
    the driver runs vmapped-conv clients the XLA partitioner cannot
    auto-shard over ``pod``. ``axis_name=None`` keeps the plain stacked
    layout for GSPMD auto-partitioning (the LM dry-run path).

    ``with_churn`` appends two (N,) bool operands to whichever surface
    was selected — ``round_step(..., present, agg_present)``: the
    fault-injection regime of the fleet driver. ``agg_present`` gates
    the incoming Eq. 2 (who *receives* the previous round's decision —
    the masked aggregation variants, with the driver's host-computed
    staleness weights riding the existing ``weights`` operand) and
    ``present`` masks this round's local phase (dropped pods run
    masked no-op steps). All-ones masks reproduce the churn-free body
    bitwise, so the driver uses one program for both regimes.

    ``hier_k_local > 0`` selects the HIERARCHICAL surface instead (it
    implies the in-program eval and is exclusive with
    ``with_eval``/``with_loss``): the stat upload never leaves the
    mesh — each pod runs a local ``k_local``-means over its members'
    stats in-program and only the O(pods * k_local)
    :class:`HierRoundOut` summaries cross to the host, which answers
    with a (S,) pod-cluster -> global-cluster map ``g`` instead of a
    (N,) client decision. The signature becomes::

        round_step(sparams, sopt, batch, val, lr, g, use_composed,
                   clusters0, a_prev, kmkey, weights[, present,
                   agg_present, report]) -> (sparams, sopt,
                                             HierRoundOut)

    The incoming Eq. 2 decision is composed IN-PROGRAM:
    ``where(use_composed, g[a_prev], clusters0)`` — ``a_prev`` is the
    previous round's device-resident ``a_local`` feedback, ``clusters0``
    a device-resident fallback (the driver feeds singletons, making
    round 0's aggregation the bitwise identity exactly like the flat
    driver), and ``use_composed`` a traced () bool that flips after
    round 0 — so neither the O(N) fallback nor the assignments ever
    cross the host boundary per round. ``kmkey`` seeds pod ``p``'s
    k-means via ``fold_in(kmkey, p)`` (the pod index is
    ``axis_index(axis_name)`` under shard_map, the static loop index on
    the GSPMD path, where ``hier_pods`` must divide the client count
    into equal contiguous pods). With ``with_churn`` a THIRD mask
    ``report`` joins ``(present, agg_present)``: it masks the pod
    k-means and the summary sums — a straggler trains but misses the
    summary deadline, so the hier coordinator sees only fresh reports
    (there is no per-client last-seen cache host-side; that cache is
    O(clients), the very thing this surface removes).
    """
    step = make_train_step(model, opt)

    def body(sparams, sopt, batch, lr, clusters, weights,
             present=None, agg_present=None):
        # Eq. 2 on the incoming (previous-round) coordinator decision
        if agg_present is not None:
            if axis_name is None:
                sparams = cluster_fedavg_masked(sparams, clusters, weights,
                                                agg_present, k=k)
            else:
                sparams = cluster_fedavg_psum_masked(
                    sparams, clusters, weights, agg_present, k=k,
                    axis_name=axis_name)
        elif axis_name is None:
            sparams = cluster_fedavg(sparams, clusters, weights, k=k)
        else:
            sparams = cluster_fedavg_psum(sparams, clusters, weights, k=k,
                                          axis_name=axis_name)
        # ceil-sized microbatches with a clamped final start cover every
        # row (indivisible batches overlap slightly at the tail instead
        # of silently dropping rows); training n_local_steps times on
        # the identical batch would not be SGD.
        n_b = jax.tree.leaves(batch)[0].shape[1]
        mb = min(n_b, -(-n_b // n_local_steps))

        def batch_for_step(i):
            start = jnp.minimum(i * mb, n_b - mb)
            return jax.tree.map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, start, mb, 1),
                batch)

        sparams, sopt, losses = local_phase(step, sparams, sopt, lr,
                                            jnp.arange(n_local_steps),
                                            batch_for_step, present=present)
        stats = swarm_distribution_matrix(sparams, use_pallas=use_pallas)
        return sparams, sopt, stats, losses

    if hier_k_local > 0:
        if with_eval or with_loss:
            raise ValueError("hier_k_local selects its own eval surface "
                             "— drop with_eval/with_loss")
        kl = int(hier_k_local)
        client_eval = make_client_eval(model)

        def _pod_summary(stats, val_acc, weights, report, key, pod_idx):
            C, a = kmeans(key, stats, k=kl, iters=hier_kmeans_iters,
                          mask=report)
            w = (jnp.ones(stats.shape[:1], stats.dtype) if report is None
                 else jnp.asarray(report, stats.dtype))
            counts = jax.ops.segment_sum(w, a, kl)
            wsums = jax.ops.segment_sum(weights * w, a, kl)
            valsums = jax.ops.segment_sum(val_acc * w, a, kl)
            pc = pod_idx * kl + a.astype(jnp.int32)
            return C, counts, wsums, valsums, pc

        def round_step_hier(sparams, sopt, batch, val, lr, g, use_comp,
                            clusters0, a_prev, kmkey, weights,
                            *churn_masks):
            kw = {}
            report = None
            if with_churn:
                present, agg_present, report = churn_masks
                kw = {"present": present, "agg_present": agg_present}
            # the incoming decision, composed on-mesh: round 0 rides the
            # device-resident fallback (the driver feeds singletons — the
            # bitwise-identity Eq. 2, exactly the flat driver's round 0)
            clusters = jnp.where(use_comp, g[a_prev], clusters0)
            sparams, sopt, stats, losses = body(
                sparams, sopt, batch, lr, clusters, weights, **kw)
            val_acc = client_eval(sparams, val)
            loss = losses[-1]
            mean_val = jnp.mean(val_acc)
            if axis_name is not None:
                loss = jax.lax.pmean(loss, axis_name)
                mean_val = jax.lax.pmean(mean_val, axis_name)
                pod = jax.lax.axis_index(axis_name)
                C, counts, wsums, valsums, pc = _pod_summary(
                    stats, val_acc, weights, report,
                    jax.random.fold_in(kmkey, pod), pod)
            else:
                n_loc = stats.shape[0]
                P = int(hier_pods)
                if P <= 0 or n_loc % P:
                    raise ValueError(
                        "the GSPMD hier surface needs hier_pods to "
                        f"divide the client count into equal contiguous "
                        f"pods (hier_pods={P}, clients={n_loc})")
                m = n_loc // P
                outs = []
                for p in range(P):
                    sl = slice(p * m, (p + 1) * m)
                    outs.append(_pod_summary(
                        stats[sl], val_acc[sl], weights[sl],
                        None if report is None else report[sl],
                        jax.random.fold_in(kmkey, p), p))
                C = jnp.concatenate([o[0] for o in outs], axis=0)
                counts = jnp.concatenate([o[1] for o in outs])
                wsums = jnp.concatenate([o[2] for o in outs])
                valsums = jnp.concatenate([o[3] for o in outs])
                pc = jnp.concatenate([o[4] for o in outs])
            return sparams, sopt, HierRoundOut(
                centroids=C, counts=counts, wsums=wsums, valsums=valsums,
                a_local=pc, mean_val=mean_val, train_loss=loss)

        return round_step_hier

    if with_eval:
        client_eval = make_client_eval(model)

        def round_step_eval(sparams, sopt, batch, val, lr, clusters,
                            weights, *churn_masks):
            kw = {}
            if with_churn:
                present, agg_present = churn_masks
                kw = {"present": present, "agg_present": agg_present}
            sparams, sopt, stats, losses = body(sparams, sopt, batch, lr,
                                                clusters, weights, **kw)
            val_acc = client_eval(sparams, val)
            loss = losses[-1]
            if axis_name is not None:
                # per-shard means -> the global mean (equal local counts)
                loss = jax.lax.pmean(loss, axis_name)
            return sparams, sopt, FleetRoundOut(stats=stats,
                                                val_acc=val_acc,
                                                train_loss=loss)

        return round_step_eval

    if with_loss:

        def round_step_loss(sparams, sopt, batch, lr, clusters, weights,
                            *churn_masks):
            kw = {}
            if with_churn:
                present, agg_present = churn_masks
                kw = {"present": present, "agg_present": agg_present}
            sparams, sopt, stats, losses = body(sparams, sopt, batch, lr,
                                                clusters, weights, **kw)
            loss = losses[-1]
            if axis_name is not None:
                loss = jax.lax.pmean(loss, axis_name)
            return sparams, sopt, stats, loss

        return round_step_loss

    def round_step(sparams, sopt, batch, lr, clusters, weights,
                   *churn_masks):
        kw = {}
        if with_churn:
            present, agg_present = churn_masks
            kw = {"present": present, "agg_present": agg_present}
        sparams, sopt, stats, _ = body(sparams, sopt, batch, lr, clusters,
                                       weights, **kw)
        return sparams, sopt, stats

    return round_step
