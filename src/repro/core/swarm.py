"""Stateful host wrapper over the functional round engine (sim regime).

Since the engine redesign, all round logic lives in
:mod:`repro.core.engine`: an explicit :class:`~repro.core.engine.SwarmState`
pytree and the pure ``swarm_round(state, data, cfg)`` function, jit'd
into ONE device program per round (and scannable over rounds via
``run_rounds``). :class:`SwarmTrainer` is the thin stateful shell that
remains for host-driven use — it owns a ``SwarmState``, advances it one
engine call per round, and keeps the familiar surface:

  ``round`` / ``fit``        — advance the protocol, appending
                               :class:`RoundLog` entries to ``history``
  ``fit_scanned``            — the same rounds as one scanned program
  ``client_scores``          — per-client masked accuracy on any split
  ``aggregation`` mode       — "bso" (full §III round), "fedavg"
                               (federated baseline), "none" (isolation)

(The centralized baseline pools data and is in baselines.py.) Batch
sampling, the brain-storm decision, k-means and Eq. 2 all execute
on-device inside the engine program; the only host-side residue is the
conversion of per-round metrics into ``RoundLog``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import OptimizerConfig, SwarmConfig
from repro.core.engine import (EngineConfig, RoundMetrics, SwarmState,
                               eval_swarm, jit_run_rounds, jit_swarm_round,
                               make_batch, make_client_eval, make_swarm_data,
                               make_swarm_state, pad_eval_split,
                               resolve_local_steps, stack_eval_split)
from repro.models.model import Model
from repro.optim.optimizers import make_optimizer
from repro.train.steps import make_eval_step


def eval_client(eval_fn, cfg, params, X, y, batch: int = 64) -> float:
    """Masked fixed-shape evaluation of ONE client (pads with label=-1).

    Kept for the centralized baseline and as the parity oracle for the
    engine's vmapped client-axis eval (:func:`make_client_eval`)."""
    n = len(y)
    correct, total = 0.0, 0
    for s in range(0, n, batch):
        k = len(y[s:s + batch])
        xb, yb = pad_eval_split(X[s:s + batch], y[s:s + batch], batch)
        m = eval_fn(params, make_batch(cfg, xb, yb))
        correct += float(m["acc"]) * k
        total += k
    return correct / max(total, 1)


@dataclass
class RoundLog:
    round: int
    mean_val_acc: float
    assignments: np.ndarray
    centers: np.ndarray
    events: List[str]
    train_loss: float


def _round_log(r: int, m: RoundMetrics) -> RoundLog:
    events = (["replace"] * int(m.n_replaced) + ["swap"] * int(m.n_swapped))
    return RoundLog(r, float(m.mean_val_acc), np.asarray(m.assignments),
                    np.asarray(m.centers), events, float(m.train_loss))


class SwarmTrainer:
    def __init__(self, model: Model, clients_data: List[dict],
                 swarm: SwarmConfig, opt_cfg: OptimizerConfig,
                 key, *, batch_size: int = 16, aggregation: str = "bso",
                 lr: Optional[float] = None, reset_opt_each_round: bool = False,
                 use_pallas: bool = False):
        assert aggregation in ("bso", "fedavg", "none")
        self.model = model
        self.cfg = model.cfg
        self.data = clients_data
        self.swarm = swarm
        self.n = len(clients_data)
        self.batch_size = batch_size
        self.aggregation = aggregation
        self.lr = lr if lr is not None else opt_cfg.lr
        self.opt = make_optimizer(opt_cfg)
        self.n_samples = np.array([c["n_train"] for c in clients_data],
                                  np.float32)

        self.engine_cfg = EngineConfig(
            model=model, opt=self.opt, local_steps=self._local_steps(),
            batch_size=batch_size, lr=self.lr, aggregation=aggregation,
            n_clusters=swarm.n_clusters, p1=swarm.p1, p2=swarm.p2,
            kmeans_iters=swarm.kmeans_iters, use_pallas=use_pallas,
            reset_opt_each_round=reset_opt_each_round)
        self.swarm_data = make_swarm_data(self.cfg, clients_data)
        self.state: SwarmState = make_swarm_state(model, self.opt,
                                                  clients_data, key)
        # static arguments of the bso.round span: the val slots eval
        # scores a round, and how many of them are real images
        self._round_args = {
            "eval_rows": sum(math.prod(v["labels"].shape[:3])
                             for v in self.swarm_data.val),
            "eval_real_rows": sum(len(c["val"][1]) for c in clients_data)}

        # _eval stays public-ish: eval_client(tr._eval, ...) is the
        # per-client parity oracle used by tests and coordinator_bench
        self._eval = jax.jit(make_eval_step(model))
        self._veval = jax.jit(make_client_eval(model))
        # val is scored as the round scores it, over the engine's groups
        self._eval_val = jax.jit(lambda p, d: eval_swarm(model, p, d))
        self._eval_splits: Dict[str, dict] = {}
        self.history: List[RoundLog] = []

    # engine state passthroughs (the state pytree is the truth)
    @property
    def params(self):
        return self.state.params

    @property
    def opt_state(self):
        return self.state.opt_state

    # ---------------------------------------------------------------- local
    def _local_steps(self) -> int:
        return resolve_local_steps(self.swarm, self.data, self.batch_size)

    # ----------------------------------------------------------------- eval
    def client_scores(self, split: str = "val") -> np.ndarray:
        """Per-client masked accuracy — ONE vmapped device program over
        the client axis per split (eval data is static, so the
        device-resident stack is built once per split); ``"val"`` is
        the engine's own eval over :attr:`swarm_data`'s val groups."""
        if split == "val":
            scores = self._eval_val(self.state.params, self.swarm_data)
            return np.asarray(scores, np.float32)
        if split not in self._eval_splits:
            self._eval_splits[split] = stack_eval_split(self.cfg, self.data,
                                                        split)
        scores = self._veval(self.state.params, self._eval_splits[split])
        return np.asarray(scores, np.float32)

    def mean_accuracy(self, split: str = "test") -> float:
        """Paper Eq. 3: average of per-client accuracy."""
        return float(self.client_scores(split).mean())

    # ---------------------------------------------------------------- round
    def round(self, r: int, key=None) -> RoundLog:
        """Round ``r``, one engine dispatch; ``key`` starts a key chain
        (None continues the state's). Traced as the host span ``bso.round``
        (step ``r``, with the static ``eval_rows`` and ``eval_real_rows``)
        holding ``bso.dispatch`` and ``bso.round_log``."""
        with jax.profiler.StepTraceAnnotation("bso.round", step_num=r,
                                              **self._round_args):
            if key is not None:   # copied: the engine donates the state
                self.state = self.state._replace(key=jnp.copy(jnp.asarray(key)))
            with jax.profiler.TraceAnnotation("bso.dispatch"):
                self.state, m = jit_swarm_round(self.state, self.swarm_data,
                                                self.engine_cfg)
            with jax.profiler.TraceAnnotation("bso.round_log"):
                log = _round_log(r, m)
            self.history.append(log)
        return log

    def fit(self, key, rounds: Optional[int] = None, verbose: bool = False):
        """Round-by-round fit on ONE key schedule: the caller's key
        seeds the engine chain once and every round's keys derive
        in-program from the carried state key — the identical schedule
        :meth:`fit_scanned`'s scan advances, so the two are bitwise
        interchangeable (``tests/test_sweep.py`` pins this)."""
        rounds = rounds or self.swarm.rounds
        start = len(self.history)
        for r in range(start, start + rounds):
            log = self.round(r, key if r == start else None)
            if verbose:
                print(f"[{self.aggregation}] round {r:3d} "
                      f"val_acc={log.mean_val_acc:.4f} loss={log.train_loss:.4f} "
                      + ("; ".join(log.events) if log.events else ""))
        return self.history

    def fit_scanned(self, key, rounds: Optional[int] = None):
        """The same rounds as :meth:`fit`, but scanned into ONE device
        program (``engine.run_rounds``) — no per-round host dispatch."""
        rounds = rounds or self.swarm.rounds
        state = self.state._replace(key=jnp.copy(key))
        self.state, ms = jit_run_rounds(state, self.swarm_data,
                                        self.engine_cfg, rounds)
        start = len(self.history)
        for i in range(rounds):
            self.history.append(
                _round_log(start + i, jax.tree.map(lambda x: x[i], ms)))
        return self.history
