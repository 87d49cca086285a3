"""Fleet-driver tests: the end-to-end multi-round BSO-SL loop (PR 5).

Covers the acceptance properties of ``repro.launch.fleet_driver``:

* the driver runs full rounds with exactly ONE compiled fleet-round
  executable, threading each round's host coordinator decision into
  the next round's clusters (the stats -> k-means/BSA -> clusters loop
  the ROADMAP fleet item asked for),
* the host coordinator is deterministic given the uploaded stats, and
  the driver's per-round assignments are exactly host ``kmeans`` +
  numpy ``brain_storm`` on the stats it pulled,
* donated-buffer reuse across rounds never retraces (jit cache-size),
* sim parity: at unit scale the driver's val-acc trajectory matches
  the sim engine's ``run_rounds`` statistically (same protocol; the
  RNG streams differ — host batch sampling and the numpy brain storm
  vs the engine's in-program draws — the same documented caveat as the
  numpy-oracle parity in ``tests/test_engine.py``).

Runs on whatever backend pytest sees: under ``./test.sh`` the 8-device
stand-in gives one clinic per device; under plain ``pytest`` the same
driver code runs on the trivial single-device pod mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import OptimizerConfig
from repro.core.bso import brain_storm
from repro.core.engine import (EngineConfig, jit_run_rounds, make_swarm_data,
                               make_swarm_state, stack_eval_split)
from repro.core.kmeans import kmeans
from repro.data.dr import TABLE_I, make_dr_swarm_data
from repro.launch.fleet_driver import (host_coordinator, make_unit_fleet,
                                       run_fleet, _sample_round_batch)
from repro.launch.mesh import make_fleet_mesh
from repro.launch.swarm_fleet import fleet_setup
from repro.models import build_model
from repro.optim.optimizers import make_optimizer
from repro.sharding import use_sharding

N_CLIENTS = 8
SMALL_TABLE = np.maximum(TABLE_I // 16,
                         (TABLE_I > 0).astype(np.int64) * 2)[:, :N_CLIENTS]


@pytest.fixture(scope="module")
def unit_clients():
    return make_dr_swarm_data(image_size=16, seed=0, table=SMALL_TABLE)


@pytest.fixture(scope="module")
def unit_model():
    return build_model(get_config("squeezenet-dr"))


def _opt():
    return make_optimizer(OptimizerConfig(name="adam", lr=2e-3))


def test_fleet_driver_smoke(unit_model, unit_clients):
    """Tier-1 stage-4 smoke: 2 driver rounds, ONE compiled round step,
    well-formed protocol artifacts, and the loop actually closed (round
    1 aggregates round 0's coordinator decision)."""
    mesh = make_fleet_mesh(len(unit_clients))
    res = run_fleet(unit_model, _opt(), mesh, unit_clients, rounds=2,
                    local_steps=2, batch_size=8, seed=0)
    assert res.n_compiles == 1
    assert len(res.history) == 2
    for log in res.history:
        assert 0.0 <= log.mean_val_acc <= 1.0
        assert np.isfinite(log.train_loss)
        assert log.stats.shape[0] == len(unit_clients)
        assert log.stats.ndim == 2 and log.stats.shape[1] % 2 == 0
        assert set(log.assignments.tolist()) <= {0, 1, 2}
    # round 0 is seeded with the identity plan; round 1 applies the
    # clusters decided from round 0's stat upload
    np.testing.assert_array_equal(res.history[0].applied_clusters,
                                  np.arange(len(unit_clients)))
    np.testing.assert_array_equal(res.history[1].applied_clusters,
                                  res.history[0].assignments)


def test_fleet_driver_three_rounds_coordinator_loop(unit_model,
                                                    unit_clients):
    """Acceptance: >= 3 full rounds, one executable, and per round the
    recorded cluster decision is EXACTLY host k-means + numpy
    brain_storm on the stats/val scores the driver pulled — replayed
    both through ``host_coordinator`` (determinism) and through the
    underlying pieces directly (the contract is the paper's
    neighbour-assignment server, not a private code path)."""
    seed, k, p1, p2, iters = 3, 3, 0.9, 0.8, 20
    mesh = make_fleet_mesh(len(unit_clients))
    res = run_fleet(unit_model, _opt(), mesh, unit_clients, rounds=3,
                    local_steps=2, batch_size=8, seed=seed, n_clusters=k,
                    p1=p1, p2=p2, kmeans_iters=iters)
    assert res.n_compiles == 1 and len(res.history) == 3
    for r, log in enumerate(res.history):
        # deterministic replay through the coordinator entry point
        a1, c1, _ = host_coordinator(log.stats, log.val_acc, k=k, p1=p1,
                                     p2=p2, kmeans_iters=iters, seed=seed,
                                     round_idx=r)
        np.testing.assert_array_equal(a1, log.assignments)
        np.testing.assert_array_equal(c1, log.centers)
        # independent replay through kmeans + brain_storm themselves
        key = jax.random.fold_in(jax.random.PRNGKey(seed), r)
        _, a0 = kmeans(key, jnp.asarray(log.stats, jnp.float32), k=k,
                       iters=iters)
        plan = brain_storm(np.random.default_rng([seed, r]),
                           np.asarray(a0), log.val_acc, k, p1, p2)
        np.testing.assert_array_equal(plan.assignments, log.assignments)
        np.testing.assert_array_equal(plan.centers, log.centers)
        # and the loop closure: decision r aggregates in round r+1
        if r + 1 < len(res.history):
            np.testing.assert_array_equal(res.history[r + 1].applied_clusters,
                                          log.assignments)


def test_fleet_round_donated_reuse_does_not_retrace(unit_model,
                                                    unit_clients):
    """Round-over-round reuse of the donated params/opt buffers with
    fresh host batches and fresh cluster plans must hit the jit cache:
    ONE traced/compiled program for any number of rounds."""
    N = len(unit_clients)
    mesh = make_fleet_mesh(N)
    opt = _opt()
    program = fleet_setup(unit_model, opt, mesh, k=N, n_local_steps=2,
                          with_eval=True, donate=True, spmd="shard_map")
    psh, osh, bsh, vsh, lsh, csh, wsh = program.in_shardings
    with mesh, use_sharding(mesh, program.rules):
        keys = jax.random.split(jax.random.PRNGKey(0), N)
        sparams = jax.device_put(jax.vmap(unit_model.init)(keys), psh)
        sopt = jax.device_put(jax.vmap(opt.init)(sparams), osh)
        val = jax.device_put(
            stack_eval_split(unit_model.cfg, unit_clients, "val"), vsh)
        weights = jax.device_put(
            jnp.asarray([c["n_train"] for c in unit_clients], jnp.float32),
            wsh)
        lr = jax.device_put(jnp.float32(2e-3), lsh)
        rng = np.random.default_rng(0)
        for r in range(3):
            batch = jax.device_put(
                _sample_round_batch(unit_model.cfg, unit_clients, 16,
                                    seed=0, round_idx=r), bsh)
            clusters = jax.device_put(
                jnp.asarray(rng.integers(0, 3, size=N), jnp.int32), csh)
            sparams, sopt, out = program.jit_fn(sparams, sopt, batch, val,
                                                lr, clusters, weights)
            assert np.isfinite(float(out.train_loss))
            assert program.jit_fn._cache_size() == 1, \
                f"fleet round retraced at round {r}"


def test_fleet_driver_bucketed_eval_parity(unit_model, unit_clients):
    """Bucketed ragged eval on the driver: the round program carries no
    rectangular val stack (with_loss surface), each size bucket gets
    ONE fixed-shape compiled eval program, and every round's val
    accuracies / coordinator decisions / losses match the in-program
    rectangular eval exactly — at a compile budget of 1 + n_buckets
    with zero per-round retraces."""
    mesh = make_fleet_mesh(len(unit_clients))
    kw = dict(rounds=2, local_steps=2, batch_size=8, seed=0)
    res_r = run_fleet(unit_model, _opt(), mesh, unit_clients, **kw)
    res_b = run_fleet(unit_model, _opt(), mesh, unit_clients,
                      eval_buckets=3, **kw)
    n_buckets = res_b.meta["eval_buckets"]
    assert 2 <= n_buckets <= 3
    assert res_b.n_compiles == 1 + n_buckets
    assert res_r.meta["eval_buckets"] == 0 and res_r.n_compiles == 1
    for lr_, lb in zip(res_r.history, res_b.history):
        np.testing.assert_array_equal(lr_.val_acc, lb.val_acc)
        np.testing.assert_array_equal(lr_.assignments, lb.assignments)
        np.testing.assert_array_equal(lr_.stats, lb.stats)
        assert lr_.train_loss == lb.train_loss


@pytest.mark.parametrize("seed", [
    0,
    pytest.param(7, marks=pytest.mark.slow),
    pytest.param(23, marks=pytest.mark.slow),
])
def test_fleet_driver_matches_sim_engine_statistically(unit_model,
                                                       unit_clients, seed):
    """Sim parity: the driver executes the engine's protocol sequence
    (train -> eval -> stats -> coordinator -> Eq. 2 per round, with the
    driver's final Eq. 2 pending), so at unit scale the two val-acc
    trajectories must agree statistically — different RNG streams, same
    documented caveat as the engine's numpy-oracle parity. Tier-1 runs
    the pinned seed; the slow replicas (nightly ``--runslow``) guard
    against the one-seed pass being luck."""
    rounds, local_steps = 4, 10
    mesh = make_fleet_mesh(len(unit_clients))
    res = run_fleet(unit_model, _opt(), mesh, unit_clients, rounds=rounds,
                    local_steps=local_steps, batch_size=8, seed=seed)
    fleet = res.mean_val_accs

    opt = _opt()
    cfg = EngineConfig(model=unit_model, opt=opt, local_steps=local_steps,
                       batch_size=8, lr=2e-3, aggregation="bso",
                       n_clusters=3, p1=0.9, p2=0.8, kmeans_iters=20)
    data = make_swarm_data(unit_model.cfg, unit_clients)
    state = make_swarm_state(unit_model, opt, unit_clients,
                             jax.random.PRNGKey(seed))
    _, ms = jit_run_rounds(state, data, cfg, rounds)
    sim = np.asarray(ms.mean_val_acc).tolist()

    # both learn past the 5-class random floor by the end...
    assert np.mean(fleet[-2:]) > 0.25, (fleet, sim)
    assert np.mean(sim[-2:]) > 0.25, (fleet, sim)
    # ...and the settled halves of the trajectories agree
    assert abs(np.mean(fleet[-2:]) - np.mean(sim[-2:])) < 0.2, (fleet, sim)


def test_unit_fleet_builder_shapes():
    """make_unit_fleet clips the Table-I clinic axis and builds a pod
    mesh whose client axis divides the clinic count."""
    model, opt, mesh, clients = make_unit_fleet(n_clients=4, image_size=8,
                                                data_scale=32)
    assert len(clients) == 4
    assert 4 % mesh.shape["pod"] == 0
    assert tuple(mesh.axis_names) == ("pod", "data", "model")
    assert model.cfg.arch_id == "squeezenet-dr"
