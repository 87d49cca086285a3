"""The grouped val layout of :class:`~repro.core.engine.SwarmData`.

``make_swarm_data`` stacks each clinic's val split with the clinics
that need the same number of eval microbatches, so a skewed swarm
(paper Table I: one clinic with 97 val images, the rest 1-64) scores
no all-pad microbatch. Pinned here:

* the groups partition the clients by microbatch count,
* ``eval_swarm`` over the groups is BITWISE ``make_client_eval`` over
  the one rectangular ``stack_eval_split`` stack,
* a uniform swarm is one ``range(N)`` group whose eval lowers with no
  scatter,
* a ``SwarmTrainer`` fit over the groups is bitwise the same fit over
  the rectangular stack, and ``client_scores("val")`` reads the groups.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import OptimizerConfig, SwarmConfig
from repro.core.engine import (SwarmData, eval_swarm, make_client_eval,
                               make_swarm_data, stack_eval_split)
from repro.core.swarm import SwarmTrainer
from repro.data.dr import TABLE_I, make_dr_swarm_data
from repro.models import build_model

SMALL_TABLE = np.maximum(TABLE_I // 16, (TABLE_I > 0).astype(np.int64) * 2)
N = TABLE_I.shape[1]


@pytest.fixture(scope="module")
def dr_model():
    return build_model(get_config("squeezenet-dr"))


@pytest.fixture(scope="module")
def dr_clients():
    return make_dr_swarm_data(image_size=8, seed=0, table=SMALL_TABLE)


@pytest.fixture(scope="module")
def c3_clients():
    """Table I's skew at the default microbatch: clinic C3 at full size
    (97 val images, two microbatches of 64), the rest cut 16x (one)."""
    table = SMALL_TABLE.copy()
    table[:, 2] = TABLE_I[:, 2]
    return make_dr_swarm_data(image_size=8, seed=0, table=table)


def rectangular(cfg, clients, eval_batch: int = 64) -> SwarmData:
    """``data`` with its val as ONE stack padded to the largest client."""
    data = make_swarm_data(cfg, clients, eval_batch=eval_batch)
    return SwarmData(data.train, data.train_n,
                     [stack_eval_split(cfg, clients, "val", eval_batch)],
                     [range(len(clients))])


def _params(model, n, seed):
    return jax.vmap(model.init)(jax.random.split(jax.random.PRNGKey(seed), n))


@pytest.mark.parametrize("eval_batch", [2, 4])
def test_val_groups_partition_by_microbatch_count(dr_model, dr_clients,
                                                  eval_batch):
    data = make_swarm_data(dr_model.cfg, dr_clients, eval_batch=eval_batch)
    assert sorted(i for g in data.val_ids for i in g) == list(range(N))
    counts = [-(-len(c["val"][1]) // eval_batch) for c in dr_clients]
    seen = []
    for ids, val_g in zip(data.val_ids, data.val):
        assert list(ids) == sorted(ids)
        m = {counts[i] for i in ids}
        assert len(m) == 1
        assert val_g["labels"].shape[:3] == (len(ids), m.pop(), eval_batch)
        seen.append(counts[ids[0]])
    assert seen == sorted(set(counts))


@pytest.mark.parametrize("eval_batch", [2, 4])
def test_eval_swarm_groups_bitwise_one_stack(dr_model, dr_clients,
                                             eval_batch):
    """Table-I skew at a small microbatch: C3 (6 val images) alone needs
    the most microbatches; the grouped eval scores every clinic bitwise
    as the one vmapped program over the rectangular stack does."""
    data = make_swarm_data(dr_model.cfg, dr_clients, eval_batch=eval_batch)
    assert len(data.val_ids) > 1 and data.val_ids[-1] == (2,)
    params = _params(dr_model, N, 3)
    stack = stack_eval_split(dr_model.cfg, dr_clients, "val", eval_batch)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda p, d: eval_swarm(dr_model, p, d))(
            params, data)),
        np.asarray(jax.jit(make_client_eval(dr_model))(params, stack)))


def test_uniform_swarm_is_one_group_without_scatter(dr_model, dr_clients):
    clients = [dr_clients[0]] * 4
    data = make_swarm_data(dr_model.cfg, clients, eval_batch=2)
    assert data.val_ids == ((0, 1, 2, 3),)
    params = _params(dr_model, 4, 4)
    lowered = jax.jit(lambda p, d: eval_swarm(dr_model, p, d)).lower(
        params, data)
    assert "scatter" not in lowered.as_text()
    stack = stack_eval_split(dr_model.cfg, clients, "val", 2)
    np.testing.assert_array_equal(np.asarray(data.val[0]["images"]),
                                  np.asarray(stack["images"]))


def _trainer(model, clients):
    swarm = SwarmConfig(n_clients=len(clients), n_clusters=3, rounds=2,
                        local_steps=2, kmeans_iters=5)
    return SwarmTrainer(model, clients, swarm,
                        OptimizerConfig(name="adam", lr=2e-3),
                        jax.random.PRNGKey(0), batch_size=8,
                        aggregation="bso")


def test_trainer_fit_groups_bitwise_one_stack(dr_model, c3_clients):
    """A 2-round fit over the grouped val (C3 alone in two microbatches,
    13 clinics in one) is bitwise the fit over the rectangular stack."""
    grouped = _trainer(dr_model, c3_clients)
    assert grouped.swarm_data.val_ids == (
        tuple(i for i in range(N) if i != 2), (2,))
    rect = _trainer(dr_model, c3_clients)
    rect.swarm_data = rectangular(dr_model.cfg, c3_clients)
    key = jax.random.PRNGKey(9)
    for a, b in zip(grouped.fit(key), rect.fit(key), strict=True):
        assert (a.round, a.mean_val_acc, a.train_loss, a.events) == (
            b.round, b.mean_val_acc, b.train_loss, b.events)
        np.testing.assert_array_equal(a.assignments, b.assignments)
        np.testing.assert_array_equal(a.centers, b.centers)
    for x, y in zip(jax.tree.leaves(grouped.params),
                    jax.tree.leaves(rect.params), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(grouped.client_scores("val"),
                                  rect.client_scores("val"))
    # the span arguments: 13 clinics x 1 and C3 x 2 microbatches of 64
    assert grouped._round_args == {
        "eval_rows": (13 + 2) * 64,
        "eval_real_rows": sum(len(c["val"][1]) for c in c3_clients)}
