"""Table II methods and hyper-parameter grids as thin slices of the
sweep/grid engine.

Since the method-axis redesign, all four paper methods (centralized /
local / FedAvg / BSO-SL) are parameterisations of the one fused round
in :mod:`repro.core.engine` (:class:`~repro.core.engine.MethodParams`),
and since the grid redesign the BSO knobs the paper fixes (k, p1, p2,
plus local-step/lr budgets) are too
(:class:`~repro.core.engine.GridPoint`). This module is the
host-facing surface over those axes. Which entry point to use:

* :func:`run_method`  — ONE paper method, one scanned ``run_rounds``
  program for the whole fit. Use it when you want a single Table-II
  row (or the serial parity reference for a sweep row —
  ``tests/test_sweep.py`` pins sweep row m == ``run_method`` bitwise).
* :func:`run_sweep_table` — the whole Table II *method axis* as ONE
  vmapped ``run_sweep`` program sharing a single device-resident
  :class:`~repro.core.engine.SwarmData`. Use it whenever you need two
  or more methods: M methods cost one compile and one dispatch.
* :func:`run_grid_table` — a *hyper-parameter grid* (k / p1 / p2 /
  local_steps / lr axes, any method) as ONE vmapped ``run_grid``
  program. Use it for ablations: |grid| serial fits collapse into one
  executable (``BENCH_grid.json`` records the collapse).
* :func:`run_grid_point` — one grid point as a serial scanned program:
  the parity oracle for ``run_grid_table`` rows
  (``tests/test_grid.py``) and the right call for a one-off
  non-default hyper-parameter fit.
* :func:`train_centralized` — the original pooled-data host loop, kept
  as the oracle for the engine's pooled-sampling centralized method.

Note the centralized budget change: the old host loop scaled its step
count by the number of clinics; the engine's centralized row rides the
same (rounds x local_steps) grid as every other method — N replicas
sampling the pooled dataset, averaged into one global model each round
— so the axis is a controlled same-budget, same-data comparison (the
property the SL-survey literature demands of Table II-style claims).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, NamedTuple, Sequence

import jax
import numpy as np

from repro.configs.base import OptimizerConfig, SwarmConfig
from repro.core.engine import (EngineConfig, RoundMetrics, SWEEP_METHODS,
                               SwarmData, SwarmState, grid_axes, grid_point,
                               jit_run_grid, jit_run_rounds, jit_run_sweep,
                               make_bucketed_swarm_data, make_client_eval,
                               make_grid_config, make_grid_state,
                               make_swarm_data, make_swarm_state,
                               make_sweep_config, make_sweep_state,
                               method_params, resolve_local_steps,
                               stack_eval_split)
from repro.core.swarm import eval_client, make_batch
from repro.models.model import Model
from repro.optim.optimizers import make_optimizer
from repro.train.steps import make_eval_step, make_train_step


def make_method_setup(model: Model, clients_data, swarm: SwarmConfig,
                      opt_cfg: OptimizerConfig, *, batch_size: int = 16,
                      lr=None, use_pallas: bool = False,
                      cfg: EngineConfig = None, data: SwarmData = None,
                      layout: str = "rect"):
    """(EngineConfig, SwarmData) shared by every method/arch slice.
    Existing ``cfg``/``data`` pass through untouched, so repeated
    slices reuse one engine config (one compiled program) and one
    device-resident dataset — the sweep's whole point (table3 shares
    the data across architectures the same way).

    ``layout`` picks the device data layout when ``data`` is built
    here: ``"rect"`` is :class:`~repro.core.engine.SwarmData` (train
    padded to the global max, val grouped by microbatch count),
    ``"bucketed"`` the ragged
    :class:`~repro.core.engine.BucketedSwarmData` (size-bucketed pads;
    bitwise the same results — see ``tests/test_bucket.py``)."""
    if cfg is None:
        opt = make_optimizer(opt_cfg)
        cfg = EngineConfig(
            model=model, opt=opt,
            local_steps=resolve_local_steps(swarm, clients_data, batch_size),
            batch_size=batch_size, lr=lr if lr is not None else opt_cfg.lr,
            aggregation="bso", n_clusters=swarm.n_clusters, p1=swarm.p1,
            p2=swarm.p2, kmeans_iters=swarm.kmeans_iters,
            use_pallas=use_pallas)
    if data is None:
        if layout == "bucketed":
            data = make_bucketed_swarm_data(model.cfg, clients_data)
        elif layout == "rect":
            data = make_swarm_data(model.cfg, clients_data)
        else:
            raise ValueError(f"unknown layout {layout!r} "
                             "(one of 'rect', 'bucketed')")
    return cfg, data


@functools.lru_cache(maxsize=None)
def _jit_client_eval(model: Model):
    return jax.jit(make_client_eval(model))


@functools.lru_cache(maxsize=None)
def _jit_sweep_eval(model: Model):
    return jax.jit(jax.vmap(make_client_eval(model), in_axes=(0, None)))


class MethodRun(NamedTuple):
    """One finished fit: final state + the (rounds,)-stacked metrics
    (method-stacked to (M, rounds) when produced by run_sweep_table)."""
    state: SwarmState
    metrics: RoundMetrics


def sweep_keys(key, methods: Sequence = SWEEP_METHODS):
    """The per-row key schedule :func:`run_sweep_table` and
    :func:`run_grid_table` use (``methods`` is any row sequence —
    method names or grid-point specs; only its length matters) — the
    one copy, so serial parity runs reproduce row m exactly."""
    return jax.random.split(key, len(methods))


def run_method(method: str, model: Model, clients_data, swarm: SwarmConfig,
               opt_cfg: OptimizerConfig, key, *, batch_size: int = 16,
               verbose: bool = False, cfg: EngineConfig = None,
               data: SwarmData = None, test_stack=None):
    """One Table-II row. method in {centralized, local, fedavg, bso-sl}.

    The whole fit is ONE scanned device program
    (``run_rounds(..., method_params(method, N))``); the returned
    accuracy is Eq. 3 (mean per-client test accuracy) of the final
    per-client models. Pass ``cfg``/``data``/``test_stack`` from a
    previous call to share the device-resident dataset across slices.
    Returns ``(acc, MethodRun)``.
    """
    cfg, data = make_method_setup(model, clients_data, swarm, opt_cfg,
                                  batch_size=batch_size, cfg=cfg, data=data)
    state = make_swarm_state(model, cfg.opt, clients_data, key)
    state, ms = jit_run_rounds(state, data, cfg, swarm.rounds,
                               method_params(method, len(clients_data)))
    if verbose:
        for r, acc in enumerate(np.asarray(ms.mean_val_acc)):
            print(f"[{method}] round {r:3d} val_acc={acc:.4f}")
    if test_stack is None:
        test_stack = stack_eval_split(model.cfg, clients_data, "test")
    acc = float(np.mean(_jit_client_eval(model)(state.params, test_stack)))
    return acc, MethodRun(state, ms)


def run_sweep_table(model: Model, clients_data, swarm: SwarmConfig,
                    opt_cfg: OptimizerConfig, key, *,
                    methods: Sequence[str] = SWEEP_METHODS,
                    batch_size: int = 16, cfg: EngineConfig = None,
                    data: SwarmData = None, test_stack=None):
    """The whole Table II as ONE device program.

    ``key`` is split once into per-method keys (:func:`sweep_keys` —
    row m of the sweep is bitwise ``run_method(methods[m], ...,
    keys[m])``). Returns ``(accs: {method: Eq.3 test acc}, MethodRun)``
    where the MethodRun carries the (M,)-stacked final state and
    (M, rounds) metrics.
    """
    cfg, data = make_method_setup(model, clients_data, swarm, opt_cfg,
                                  batch_size=batch_size, cfg=cfg, data=data)
    keys = sweep_keys(key, methods)
    states = make_sweep_state(model, cfg.opt, clients_data, keys)
    sweep = make_sweep_config(len(clients_data), methods)
    states, ms = jit_run_sweep(states, data, cfg, sweep, swarm.rounds)
    if test_stack is None:
        test_stack = stack_eval_split(model.cfg, clients_data, "test")
    scores = np.asarray(_jit_sweep_eval(model)(states.params, test_stack))
    accs = {m: float(scores[i].mean()) for i, m in enumerate(methods)}
    return accs, MethodRun(states, ms)


def run_grid_point(spec: dict, model: Model, clients_data,
                   swarm: SwarmConfig, opt_cfg: OptimizerConfig, key, *,
                   batch_size: int = 16, cfg: EngineConfig = None,
                   data: SwarmData = None, test_stack=None):
    """One hyper-parameter point as a serial scanned program.

    ``spec`` is a :func:`~repro.core.engine.grid_point` keyword dict
    (e.g. ``{"k": 2, "p1": 1.0}``; empty = the paper point). The fit is
    ONE ``run_rounds`` program whose static maxima come from ``cfg``,
    so it is the bitwise serial slice of the corresponding
    :func:`run_grid_table` row — the grid parity oracle
    (``tests/test_grid.py``). Returns ``(acc, MethodRun)`` like
    :func:`run_method`.
    """
    cfg, data = make_method_setup(model, clients_data, swarm, opt_cfg,
                                  batch_size=batch_size, cfg=cfg, data=data)
    point = grid_point(cfg, len(clients_data), **spec)
    state = make_swarm_state(model, cfg.opt, clients_data, key)
    state, ms = jit_run_rounds(state, data, cfg, swarm.rounds, point)
    if test_stack is None:
        test_stack = stack_eval_split(model.cfg, clients_data, "test")
    acc = float(np.mean(_jit_client_eval(model)(state.params, test_stack)))
    return acc, MethodRun(state, ms)


def run_grid_table(model: Model, clients_data, swarm: SwarmConfig,
                   opt_cfg: OptimizerConfig, key, *,
                   axes: dict = None, specs: Sequence[dict] = None,
                   batch_size: int = 16, cfg: EngineConfig = None,
                   data: SwarmData = None, test_stack=None):
    """A whole hyper-parameter ablation as ONE device program —
    :func:`run_sweep_table`'s sibling for the grid axis.

    Pass either ``axes`` (named axes, expanded row-major via
    :func:`~repro.core.engine.grid_axes`, e.g.
    ``axes={"k": (1, 2, 3), "p1": (0.9, 1.0)}`` — the churn scenario
    axes ``dropout`` / ``stale_decay`` / ``churn_mask`` ride the same
    surface, so a dropout-robustness sweep is one call) or an explicit
    ``specs`` list of grid-point keyword dicts. The engine statics in
    ``cfg`` (``n_clusters``, ``local_steps``) are the grid's pads, so
    every axis value must stay within them; when ``cfg`` is built here,
    its ``n_clusters`` is raised to the largest ``k`` in the grid and
    its step budget to the largest ``local_steps`` (over the
    swarm-resolved default).

    ``key`` splits once into per-point keys (:func:`sweep_keys` — row g
    is bitwise :func:`run_grid_point` of ``specs[g]`` with ``keys[g]``;
    grids with heterogeneous ``local_steps`` ride the sorted scan
    schedule, where the contract weakens to allclose ~1 ulp — see
    :func:`~repro.core.engine._run_grid_scheduled`).
    Returns ``(results, MethodRun)`` where ``results`` is a list of
    ``{**spec, "acc": Eq.3 test acc}`` rows in grid order and the
    MethodRun carries the (G,)-stacked final state and (G, rounds)
    metrics.
    """
    if (axes is None) == (specs is None):
        raise ValueError("pass exactly one of axes= or specs=")
    if specs is None:
        specs = grid_axes(**axes)
    rows = specs
    if cfg is None:
        # pin every row's k/local_steps to the CALLER's statics before
        # raising the pads to the grid maxima — otherwise a spec that
        # omits a raised knob would silently inherit the raised value
        # instead of the paper point, breaking the run_grid_point
        # parity contract. (With an explicit cfg the statics ARE the
        # contract and rows inherit them unchanged.)
        base_steps = resolve_local_steps(swarm, clients_data, batch_size)
        rows = [{"k": swarm.n_clusters, "local_steps": base_steps, **s}
                for s in specs]
        # raise-only: the step pad fixes the PRNG split count, so
        # shrinking it below the caller's statics would break the
        # run_grid_point-with-the-same-swarm oracle
        swarm = dataclasses.replace(
            swarm,
            n_clusters=max(swarm.n_clusters,
                           *(int(r["k"]) for r in rows)),
            local_steps=max(base_steps,
                            *(int(r["local_steps"]) for r in rows)))
    cfg, data = make_method_setup(model, clients_data, swarm, opt_cfg,
                                  batch_size=batch_size, cfg=cfg, data=data)
    keys = sweep_keys(key, specs)
    states = make_grid_state(model, cfg.opt, clients_data, keys)
    grid = make_grid_config(cfg, len(clients_data), rows)
    # heterogeneous step budgets ride the sorted scan schedule (rows
    # exit the scan at their own budget instead of paying the static
    # max as masked no-ops); uniform grids keep the plain masked path.
    # Churn grids always keep the masked path — the schedule's prefix
    # segments assume every row trains every client (run_grid raises
    # on the combination)
    has_churn = any(k in r for r in rows
                    for k in ("dropout", "stale_decay", "churn_mask"))
    row_steps = tuple(int(r.get("local_steps", cfg.local_steps))
                      for r in rows)
    schedule = (row_steps if min(row_steps) < cfg.local_steps
                and not has_churn else None)
    states, ms = jit_run_grid(states, data, cfg, grid, swarm.rounds,
                              schedule)
    if test_stack is None:
        test_stack = stack_eval_split(model.cfg, clients_data, "test")
    scores = np.asarray(_jit_sweep_eval(model)(states.params, test_stack))
    results = [{**spec, "acc": float(scores[g].mean())}
               for g, spec in enumerate(specs)]
    return results, MethodRun(states, ms)


def train_centralized(model: Model, clients_data: List[dict],
                      opt_cfg: OptimizerConfig, key, *, steps: int,
                      batch_size: int = 32, lr=None):
    """Host-loop pooled-data training — the oracle the engine's
    pooled-sampling centralized method miniaturises. Returns
    (params, per-client mean test accuracy — Eq. 3 applied to the
    single global model)."""
    X = np.concatenate([c["train"][0] for c in clients_data])
    y = np.concatenate([c["train"][1] for c in clients_data])
    rng = np.random.default_rng(0)

    opt = make_optimizer(opt_cfg)
    params = model.init(key)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(model, opt))
    eval_fn = jax.jit(make_eval_step(model))
    lr = lr if lr is not None else opt_cfg.lr

    for _ in range(steps):
        idx = rng.integers(0, len(y), size=batch_size)
        params, opt_state, _ = step(params, opt_state,
                                    make_batch(model.cfg, X[idx], y[idx]), lr)

    accs = [eval_client(eval_fn, model.cfg, params, *c["test"])
            for c in clients_data]
    return params, float(np.mean(accs))
