"""Ragged bucketed-layout parity harness (PR 6).

Locks down the size-bucketed swarm layout against the rectangular
pad-to-global-max baseline:

* a full BSO-SL fit over :class:`~repro.core.engine.BucketedSwarmData`
  is BITWISE the :class:`~repro.core.engine.SwarmData` fit — sampling
  draws the identical global index tensor and bucketed eval drops only
  all-pad microbatches whose (hits, total) contribution is exactly
  +0.0,
* the pooled centralized gather (`_gather_bucketed_rows`) and the
  layout-dispatched ``eval_swarm`` are each bitwise their rectangular
  siblings,
* pad accounting: bucketing a Table-I-skewed swarm cuts the stored
  train pad fraction >= 2x (the ``BENCH_bucket.json`` acceptance
  floor),
* edge cases of the padding/sampling contracts: clients smaller than
  one eval microbatch, clients exactly at a power-of-two bucket
  boundary, a single-client swarm, and pad rows never sampled / never
  scored (label=-1 exclusion),
* the ``param_stats_batched`` Pallas kernel in ``interpret=True`` mode
  over the ragged per-bucket client stacks — the kernel path exercised
  on the bucketed shapes without a TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import OptimizerConfig
from repro.core.engine import (BucketedSwarmData, EngineConfig, eval_swarm,
                               jit_run_rounds, make_bucketed_swarm_data,
                               make_client_eval, make_swarm_data,
                               make_swarm_state, method_params, pad_fraction,
                               sample_round_batch, stack_eval_split)
from repro.data.dr import TABLE_I, bucket_clients, make_dr_swarm_data
from repro.kernels import ops, ref
from repro.models import build_model
from repro.optim.optimizers import make_optimizer

SMALL_TABLE = np.maximum(TABLE_I // 16, (TABLE_I > 0).astype(np.int64) * 2)
N = TABLE_I.shape[1]


@pytest.fixture(scope="module")
def dr_clients():
    return make_dr_swarm_data(image_size=8, seed=0, table=SMALL_TABLE)


@pytest.fixture(scope="module")
def dr_model():
    return build_model(get_config("squeezenet-dr"))


@pytest.fixture(scope="module")
def rect_data(dr_model, dr_clients):
    return make_swarm_data(dr_model.cfg, dr_clients)


@pytest.fixture(scope="module")
def buck_data(dr_model, dr_clients):
    return make_bucketed_swarm_data(dr_model.cfg, dr_clients)


def _cfg(model, **kw):
    kw.setdefault("local_steps", 2)
    kw.setdefault("kmeans_iters", 5)
    return EngineConfig(model=model, opt=make_optimizer(
        OptimizerConfig(name="adam", lr=2e-3)), batch_size=4, lr=2e-3,
        aggregation="bso", n_clusters=3, **kw)


def _params_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ----------------------------------------------------- layout invariants


def test_bucketed_layout_well_formed(dr_clients, buck_data):
    """client_ids partition range(N); each bucket's train stack is
    padded to its own ceiling, not the global maximum."""
    ids = sorted(i for b in buck_data.client_ids for i in b)
    assert ids == list(range(N))
    sizes = np.asarray(buck_data.train_n)
    n_global = int(sizes.max())
    own_ceilings = []
    for b, tr in zip(buck_data.client_ids, buck_data.train):
        stack = jax.tree.leaves(tr)[0]
        assert stack.shape[0] == len(b)
        assert stack.shape[1] == int(sizes[np.asarray(b)].max())
        own_ceilings.append(stack.shape[1])
    assert min(own_ceilings) < n_global, "bucketing did not shrink any pad"


def test_pad_fraction_reduced_2x(dr_model, dr_clients, rect_data, buck_data):
    """The acceptance floor: the Table-I size skew makes the stored
    train pad fraction drop >= 2x under bucketing; with an eval
    microbatch that does not quantise every client to one ceiling, the
    total stored-pad fraction drops >= 2x as well. ``make_swarm_data``
    groups val by microbatch count, so its eval pad is already no more
    than the buckets' and the total's drop is the train stack's."""
    pf_r, pf_b = pad_fraction(rect_data), pad_fraction(buck_data)
    assert pf_r["real_rows"] == pf_b["real_rows"]
    assert pf_b["stored_rows"] < pf_r["stored_rows"]
    assert pf_r["train"] / max(pf_b["train"], 1e-9) >= 2.0
    rect4 = make_swarm_data(dr_model.cfg, dr_clients, eval_batch=4)
    buck4 = make_bucketed_swarm_data(dr_model.cfg, dr_clients, eval_batch=4)
    pf_r4, pf_b4 = pad_fraction(rect4), pad_fraction(buck4)
    assert pf_r4["eval"] <= pf_b4["eval"]
    assert pf_r4["total"] / max(pf_b4["total"], 1e-9) >= 2.0


# ----------------------------------------------------- bitwise parity


def test_bucketed_run_rounds_bitwise_rect(dr_model, dr_clients, rect_data,
                                          buck_data):
    """The oracle: a 2-round BSO-SL fit over the bucketed layout is
    bitwise the rectangular fit — same key, same metrics, same final
    params."""
    cfg = _cfg(dr_model)
    s_r = make_swarm_state(dr_model, cfg.opt, dr_clients,
                           jax.random.PRNGKey(0))
    s_b = make_swarm_state(dr_model, cfg.opt, dr_clients,
                           jax.random.PRNGKey(0))
    s_r, m_r = jit_run_rounds(s_r, rect_data, cfg, 2)
    s_b, m_b = jit_run_rounds(s_b, buck_data, cfg, 2)
    np.testing.assert_array_equal(np.asarray(m_r.val_acc),
                                  np.asarray(m_b.val_acc))
    np.testing.assert_array_equal(np.asarray(m_r.train_loss),
                                  np.asarray(m_b.train_loss))
    np.testing.assert_array_equal(np.asarray(m_r.assignments),
                                  np.asarray(m_b.assignments))
    _params_equal(s_r.params, s_b.params)


def test_bucketed_centralized_pooled_bitwise(dr_model, dr_clients,
                                             rect_data, buck_data):
    """The pooled-sampling centralized method rides the bucketed gather
    (`_gather_bucketed_rows`): one round, bitwise params."""
    cfg = _cfg(dr_model)
    meth = method_params("centralized", N)
    s_r = make_swarm_state(dr_model, cfg.opt, dr_clients,
                           jax.random.PRNGKey(1))
    s_b = make_swarm_state(dr_model, cfg.opt, dr_clients,
                           jax.random.PRNGKey(1))
    s_r, m_r = jit_run_rounds(s_r, rect_data, cfg, 1, meth)
    s_b, m_b = jit_run_rounds(s_b, buck_data, cfg, 1, meth)
    np.testing.assert_array_equal(np.asarray(m_r.val_acc),
                                  np.asarray(m_b.val_acc))
    _params_equal(s_r.params, s_b.params)


def test_sample_round_batch_layout_bitwise(rect_data, buck_data):
    """Per-step minibatches are bitwise layout-independent, pooled or
    not, and never touch a pad row (train pads carry label=-1)."""
    for i in range(3):
        key = jax.random.PRNGKey(100 + i)
        b_r = sample_round_batch(key, rect_data, 16)
        b_b = sample_round_batch(key, buck_data, 16)
        for x, y in zip(jax.tree.leaves(b_r), jax.tree.leaves(b_b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert (np.asarray(b_b["labels"]) >= 0).all()
        for pool in (False, True):
            b_r = sample_round_batch(key, rect_data, 16, jnp.asarray(pool))
            b_b = sample_round_batch(key, buck_data, 16, jnp.asarray(pool))
            for x, y in zip(jax.tree.leaves(b_r), jax.tree.leaves(b_b)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            assert (np.asarray(b_b["labels"]) >= 0).all()


def test_eval_swarm_layout_bitwise(dr_model, dr_clients, rect_data,
                                   buck_data):
    """The bucketed masked segment reduction scores every client
    bitwise the rectangular vmapped eval."""
    params = jax.vmap(dr_model.init)(
        jax.random.split(jax.random.PRNGKey(2), N))
    a_r = eval_swarm(dr_model, params, rect_data)
    a_b = eval_swarm(dr_model, params, buck_data)
    np.testing.assert_array_equal(np.asarray(a_r), np.asarray(a_b))


# ----------------------------------------------------- edge cases


def test_client_smaller_than_one_eval_microbatch(dr_model):
    """A client with fewer rows than the eval microbatch pads to one
    batch whose tail is label=-1, and its accuracy equals the direct
    per-row accuracy over ONLY the real rows (pads never scored)."""
    table = SMALL_TABLE[:, :3]
    clients = make_dr_swarm_data(image_size=8, seed=0, table=table)
    stacked = stack_eval_split(dr_model.cfg, clients, "val", batch=64)
    labels = np.asarray(stacked["labels"])
    assert (labels == -1).any(), "expected pad rows below one microbatch"
    params = dr_model.init(jax.random.PRNGKey(0))
    sparams = jax.tree.map(lambda x: jnp.stack([x] * len(clients)), params)
    accs = np.asarray(make_client_eval(dr_model)(sparams, stacked))
    from repro.train.steps import make_eval_step
    ev = jax.jit(make_eval_step(dr_model))
    for i, c in enumerate(clients):
        X, y = c["val"]
        hits = 0
        for j in range(len(y)):
            m = ev(params, {"images": jnp.asarray(X[j:j + 1]),
                            "labels": jnp.asarray(y[j:j + 1])})
            hits += float(m["acc"])
        np.testing.assert_allclose(accs[i], hits / len(y), rtol=1e-6,
                                   atol=1e-6)


def test_client_exactly_at_bucket_boundary():
    """An exact power-of-two size is its own ceiling — it does NOT spill
    into the next bucket, so its stack carries zero pad rows."""
    groups = bucket_clients([8, 9, 16], max_buckets=4)
    as_sets = [set(g.tolist()) for g in groups]
    assert {0} in as_sets            # size 8 -> ceiling 8, alone
    assert {1, 2} in as_sets         # 9 and 16 share ceiling 16


def test_single_client_swarm(dr_model):
    """N=1: one bucket, bucketed data bitwise the rectangular data, and
    both layouts sample identical batches."""
    clients = make_dr_swarm_data(image_size=8, seed=0,
                                 table=SMALL_TABLE[:, :1])
    rect = make_swarm_data(dr_model.cfg, clients)
    buck = make_bucketed_swarm_data(dr_model.cfg, clients)
    assert buck.n_buckets == 1 and buck.client_ids == ((0,),)
    for x, y in zip(jax.tree.leaves(rect.train),
                    jax.tree.leaves(buck.train[0])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    key = jax.random.PRNGKey(4)
    b_r = sample_round_batch(key, rect, 8)
    b_b = sample_round_batch(key, buck, 8)
    for x, y in zip(jax.tree.leaves(b_r), jax.tree.leaves(b_b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    params = jax.tree.map(lambda x: x[None],
                          dr_model.init(jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(
        np.asarray(eval_swarm(dr_model, params, rect)),
        np.asarray(eval_swarm(dr_model, params, buck)))


def test_pad_rows_never_scored(dr_model, dr_clients):
    """Poisoning every pad row's inputs must not move any accuracy:
    the label=-1 mask alone decides what scores."""
    stacked = stack_eval_split(dr_model.cfg, dr_clients, "val", batch=8)
    labels = np.asarray(stacked["labels"])
    assert (labels == -1).any()
    poisoned = dict(stacked)
    imgs = np.asarray(stacked["images"]).copy()
    imgs[labels == -1] = 1e6
    poisoned["images"] = jnp.asarray(imgs)
    params = jax.vmap(dr_model.init)(
        jax.random.split(jax.random.PRNGKey(5), N))
    ev = make_client_eval(dr_model)
    np.testing.assert_array_equal(np.asarray(ev(params, stacked)),
                                  np.asarray(ev(params, poisoned)))


# ------------------------------------------- Pallas kernel on ragged stacks


def test_param_stats_batched_interpret_over_bucket_stacks(buck_data):
    """The distribution-stat kernel in interpret mode (CI has no TPU)
    over each ragged bucket stack — one (N_b, n_max_b*H*W*C) client
    matrix per bucket signature — vs the jnp oracle."""
    shapes = set()
    for tr in buck_data.train:
        x = jnp.asarray(np.asarray(tr["images"], np.float32))
        x = x.reshape(x.shape[0], -1)
        shapes.add(x.shape)
        m, v = ops.param_stats_batched(x, interpret=True)
        rm, rv = ref.ref_param_stats_batched(x)
        assert m.shape == (x.shape[0],)
        np.testing.assert_allclose(np.asarray(m), np.asarray(rm),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(v), np.asarray(rv),
                                   rtol=1e-2, atol=1e-2)
    assert len(shapes) > 1, "bucket stacks were not ragged"


def test_bucketed_data_is_a_pytree(buck_data):
    """BucketedSwarmData round-trips jax.tree.map with the static
    client_ids preserved — the jit-cache-key discipline."""
    mapped = jax.tree.map(lambda x: x, buck_data)
    assert isinstance(mapped, BucketedSwarmData)
    assert mapped.client_ids == buck_data.client_ids
    assert mapped.n_buckets == buck_data.n_buckets
