"""Plain reference of a BSO-SL swarm fit (arXiv:2404.15585, §III).

One round, for every clinic at once:

1. local phase: ``local_steps`` Adam steps, with the configuration's
   settings, on minibatches drawn uniformly with replacement from the
   clinic's own train split;
2. eval: each clinic's accuracy on its val split;
3. stat upload: per tensor, the mean and log1p of the variance of the
   clinic's parameters, tensors in the order of their path names;
4. k-means (k-means++ seeding, Lloyd iterations, an empty cluster takes
   the farthest point not yet used);
5. brain storm: each cluster's center is its member with the best val
   accuracy; with probability 1 - p1 a random member replaces it, and
   with probability 1 - p2 it swaps place and cluster with the center of
   another cluster;
6. Eq. 2: every clinic takes the train-size-weighted mean of its
   cluster's parameters.

The random draws are those of the program under test, made from the
same keys: the round's key splits into (next, local, k-means, brain
storm); each local step's key draws the (clinics, batch) row indices;
k-means++ draws its first seed and each later one from ``fold_in`` of
its key; the brain storm draws per cluster from ``fold_in`` of four
sub-keys. Nothing else is shared with the program: this module imports
none of it, and the weights come from ``init_params``.

All arithmetic is float32. The model's convolutions and matrix products
go through a ``Matmul``: the reference's multiplies float32 as it is;
the controls' in one or three bfloat16 passes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

EVAL_BATCH = 64
HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(t):
    return t.astype(jnp.bfloat16).astype(t.dtype)


class Matmul(NamedTuple):
    """How a model multiplies: ``passes`` 0 multiplies float32 as it is
    (JAX's "highest"); 1 rounds both inputs to bfloat16 and multiplies
    once, accumulating in float32 (JAX's default on a TPU); 3 splits each
    input into a bfloat16 high part and a bfloat16 remainder and sums
    three of the four products in float32 (JAX's "high" on a TPU). Each
    is spelled out so that a CPU multiplies the same way."""
    passes: int = 0
    # ``None`` leaves a float32 product to the run's default precision:
    # how the program's client model multiplies
    precision: object = HIGHEST

    def _products(self, op, x, w):
        if self.passes == 0:
            return op(x, w)
        if self.precision != HIGHEST:
            raise ValueError("bfloat16 passes are summed at HIGHEST")
        xh, wh = _bf16(x), _bf16(w)
        if self.passes == 1:
            return op(xh, wh)
        if self.passes != 3:
            raise ValueError(f"passes={self.passes}: 0, 1 or 3")
        return op(xh, wh) + op(xh, _bf16(w - wh)) + op(_bf16(x - xh), wh)

    def conv(self, x, w, stride: int = 1, padding: str = "SAME"):
        return self._products(lambda a, b: jax.lax.conv_general_dilated(
            a, b, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=self.precision), x, w)

    def dot(self, x, w):
        return self._products(
            lambda a, b: jnp.dot(a, b, precision=self.precision), x, w)


def tree_paths(tree) -> list:
    """("a/b/c", leaf) pairs of a nested dict, sorted by path."""
    out = []

    def walk(prefix, node):
        if isinstance(node, dict):
            for k in node:
                walk(f"{prefix}/{k}" if prefix else k, node[k])
        else:
            out.append((prefix, node))
    walk("", tree)
    return sorted(out, key=lambda kv: kv[0])


def unflatten(paths: dict) -> dict:
    """The nested dict of ``{"a/b/c": leaf}`` (``tree_paths`` inverted)."""
    tree = {}
    for path, value in paths.items():
        *head, last = path.split("/")
        node = tree
        for k in head:
            node = node.setdefault(k, {})
        node[last] = value
    return tree


def init_params(shapes: dict, key, n_clients: int):
    """Client-stacked weights from ``key``: He-normal conv kernels
    (fan-in kh*kw*cin), LeCun-normal dense kernels (fan-in rows), zero
    biases. Leaf i of the sorted paths draws from ``fold_in(key, i)``."""
    params = {}
    for i, (path, shape) in enumerate(tree_paths(shapes)):
        shape = tuple(shape)
        if path.endswith("/b"):
            params[path] = jnp.zeros((n_clients,) + shape, jnp.float32)
        else:
            fan_in = int(np.prod(shape[:-1]))
            gain = 2.0 if len(shape) == 4 else 1.0
            params[path] = jax.random.normal(
                jax.random.fold_in(key, i), (n_clients,) + shape,
                jnp.float32) * np.sqrt(gain / fan_in)
    return unflatten(params)


class Swarm(NamedTuple):
    """The benchmark's own device layout of the clinics' data."""
    train_x: jnp.ndarray      # (N, n_max, H, W, 3), pad rows never drawn
    train_y: jnp.ndarray      # (N, n_max)
    train_n: jnp.ndarray      # (N,) int32
    val_x: jnp.ndarray        # (N, n_mb, EVAL_BATCH, H, W, 3)
    val_y: jnp.ndarray        # (N, n_mb, EVAL_BATCH), -1 on pad rows
    n_samples: jnp.ndarray    # (N,) float32 train sizes (Eq. 2 weights)


def make_swarm(clinics: list) -> Swarm:
    def pad(X, y, n):
        k = n - len(y)
        return (np.concatenate([X, np.zeros((k,) + X.shape[1:], X.dtype)]),
                np.concatenate([y, -np.ones((k,), y.dtype)]))
    n_max = max(c["n_train"] for c in clinics)
    tx, ty = zip(*[pad(*c["train"], n_max) for c in clinics])
    v_max = max(len(c["val"][1]) for c in clinics)
    v_to = -(-v_max // EVAL_BATCH) * EVAL_BATCH
    vx, vy = zip(*[pad(*c["val"], v_to) for c in clinics])
    vx = np.stack(vx).reshape((len(clinics), -1, EVAL_BATCH) + vx[0].shape[1:])
    vy = np.stack(vy).reshape((len(clinics), -1, EVAL_BATCH))
    n = np.asarray([c["n_train"] for c in clinics])
    return Swarm(jnp.asarray(np.stack(tx)), jnp.asarray(np.stack(ty)),
                 jnp.asarray(n, jnp.int32), jnp.asarray(vx), jnp.asarray(vy),
                 jnp.asarray(n, jnp.float32))


class State(NamedTuple):
    params: dict
    m: dict
    v: dict
    t: jnp.ndarray            # () int32 Adam steps taken
    key: jnp.ndarray


def fresh_state(params, key) -> State:
    zeros = jax.tree.map(jnp.zeros_like, params)
    return State(params, zeros, zeros, jnp.zeros((), jnp.int32), key)


def _sq_dists(X, C):
    return jnp.sum((X[:, None, :] - C[None, :, :]) ** 2, axis=-1)


def kmeans(key, X, k: int, iters: int):
    """k-means++ seeding, then ``iters`` Lloyd steps; returns labels."""
    N = X.shape[0]
    first = jax.random.randint(jax.random.fold_in(key, 0), (), 0, N)
    C = jnp.zeros((k, X.shape[1]), X.dtype).at[0].set(X[first])
    for i in range(1, k):
        d = jnp.min(_sq_dists(X, C[:i]), axis=1)
        p = d / jnp.maximum(d.sum(), 1e-12)
        C = C.at[i].set(X[jax.random.choice(jax.random.fold_in(key, i), N,
                                            p=p)])

    def lloyd(_, C):
        a = jnp.argmin(_sq_dists(X, C), axis=1)
        onehot = jax.nn.one_hot(a, k, dtype=X.dtype)
        counts = onehot.sum(0)
        means = jnp.dot(onehot.T, X, precision=HIGHEST) / jnp.maximum(
            counts, 1.0)[:, None]
        # the j-th empty cluster takes the j-th point farthest from its
        # own centroid
        own = jnp.sum((X - C[a]) ** 2, axis=1)
        far = jnp.argsort(-own)
        empty = counts == 0
        rank = jnp.clip(jnp.cumsum(empty) - 1, 0, N - 1)
        return jnp.where(empty[:, None], X[far[rank]], means)
    C = jax.lax.fori_loop(0, iters, lloyd, C)
    return jnp.argmin(_sq_dists(X, C), axis=1).astype(jnp.int32)


def brain_storm(key, a, val, k: int, p1: float, p2: float):
    """Centers by best val accuracy, random replacement, then swaps in
    cluster order; returns the post-swap labels."""
    member = a[None, :] == jnp.arange(k)[:, None]
    occupied = member.any(1)
    centers = jnp.where(occupied, jnp.argmax(
        jnp.where(member, val[None, :], -jnp.inf), axis=1), -1)
    k_rep, k_member, k_swap, k_other = jax.random.split(key, 4)
    for c in range(k):
        r1 = jax.random.uniform(jax.random.fold_in(k_rep, c))
        g = jax.random.gumbel(jax.random.fold_in(k_member, c), a.shape)
        pick = jnp.argmax(jnp.where(member[c], g, -jnp.inf))
        centers = centers.at[c].set(
            jnp.where((r1 > p1) & occupied[c], pick, centers[c]))
    n_occ = occupied.sum()
    for c in range(k):
        r2 = jax.random.uniform(jax.random.fold_in(k_swap, c))
        g2 = jnp.stack([jax.random.gumbel(jax.random.fold_in(
            jax.random.fold_in(k_other, c), o)) for o in range(k)])
        others = occupied & (jnp.arange(k) != c)
        o = jnp.argmax(jnp.where(others, g2, -jnp.inf))
        swap = (r2 > p2) & occupied[c] & (n_occ > 1)
        ci, oi = centers[c], centers[o]
        a = jnp.where(swap, a.at[ci].set(a[oi]).at[oi].set(a[ci]), a)
        centers = jnp.where(swap, centers.at[c].set(oi).at[o].set(ci),
                            centers)
    return a


def _cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


class Adam(NamedTuple):
    """Adam's settings, as the configuration states them."""
    lr: float
    b1: float
    b2: float
    eps: float
    grad_clip: float


def _adam(p, m, v, g, t, opt: Adam):
    """One Adam step of one clinic, the gradient first clipped to a
    global norm of ``opt.grad_clip``."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt.grad_clip / (norm + 1e-9))
    g = jax.tree.map(lambda x: x * scale.astype(x.dtype), g)
    tf = t.astype(jnp.float32)
    bc1, bc2 = 1.0 - opt.b1 ** tf, 1.0 - opt.b2 ** tf
    m = jax.tree.map(lambda m_, g_: opt.b1 * m_ + (1 - opt.b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: opt.b2 * v_ + (1 - opt.b2) * g_ * g_,
                     v, g)
    p = jax.tree.map(
        lambda p_, m_, v_: (p_ - opt.lr * (m_ / bc1.astype(m_.dtype)) / (
            jnp.sqrt(v_ / bc2.astype(v_.dtype)) + opt.eps)).astype(p_.dtype),
        p, m, v)
    return p, m, v, g


def _features(params):
    """(N, 2 * tensors) [mean, log1p(var)] per tensor, sorted paths."""
    cols = []
    for _, leaf in tree_paths(params):
        x = leaf.astype(jnp.float32).reshape(leaf.shape[0], -1)
        mean = x.mean(1)
        cols += [mean, jnp.log1p(jnp.mean((x - mean[:, None]) ** 2, axis=1))]
    return jnp.stack(cols, axis=1)


def _accuracy(forward, mm, params, val_x, val_y):
    """One clinic's val accuracy, summed over 64-image microbatches the
    way the program reports it: each microbatch's accuracy times its
    valid count, over the valid total."""
    hits = tot = jnp.float32(0.0)
    for b in range(val_x.shape[0]):
        y = val_y[b]
        valid = y >= 0
        pred = jnp.argmax(forward(params, val_x[b], mm), axis=-1)
        n = jnp.sum(valid)
        acc = jnp.sum(valid & (pred == y)) / jnp.maximum(n, 1)
        hits = hits + acc.astype(jnp.float32) * n.astype(jnp.float32)
        tot = tot + n.astype(jnp.float32)
    return hits / jnp.maximum(tot, 1.0)


def _eq2(params, a, w, k: int):
    M = jax.nn.one_hot(a, k, dtype=jnp.float32)                  # (N, k)
    wn = w / jnp.maximum(jnp.dot(M.T, w, precision=HIGHEST), 1e-9)[a]

    def leaf(x):
        flat = x.astype(jnp.float32).reshape(x.shape[0], -1)
        agg = jnp.dot((M * wn[:, None]).T, flat, precision=HIGHEST)
        return jnp.dot(M, agg, precision=HIGHEST).reshape(x.shape).astype(x.dtype)
    return jax.tree.map(leaf, params)


class RoundOut(NamedTuple):
    loss: jnp.ndarray         # () mean over clinics of the last step's loss
    val_acc: jnp.ndarray      # (N,)
    assignments: jnp.ndarray  # (N,) post brain storm
    grad_norms: dict          # path -> norm of the round's first clipped
    #                           gradient over all clinics


@functools.partial(jax.jit, static_argnames=(
    "forward", "mm", "local_steps", "batch", "k", "iters", "p1", "p2",
    "adam", "keep"))
def swarm_round(state: State, sw: Swarm, *, forward, mm: Matmul,
                local_steps: int, batch: int, k: int, iters: int, p1: float,
                p2: float, adam: Adam, keep: int = 0):
    """One BSO-SL round. ``keep`` > 0 trains on only the first ``keep``
    rows of every minibatch (a planted fault for the benchmark's tests)."""
    next_key, k_local, k_kmeans, k_bso = jax.random.split(state.key, 4)
    N = sw.train_n.shape[0]
    rows = jnp.arange(N)[:, None]

    def client_step(p, m, v, x, y, t):
        if keep:
            x, y = x[:keep], y[:keep]

        def loss_fn(p):
            return _cross_entropy(forward(p, x, mm), y)
        loss, g = jax.value_and_grad(loss_fn)(p)
        p, m, v, g = _adam(p, m, v, g, t, adam)
        return p, m, v, loss, g

    def step(carry, s_key):
        p, m, v, t = carry
        idx = jax.random.randint(s_key, (N, batch), 0, sw.train_n[:, None])
        t = t + 1
        p, m, v, losses, g = jax.vmap(
            client_step, in_axes=(0, 0, 0, 0, 0, None))(
            p, m, v, sw.train_x[rows, idx], sw.train_y[rows, idx], t)
        norms = {path: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                 for path, x in tree_paths(g)}
        return (p, m, v, t), (jnp.mean(losses), norms)

    (p, m, v, t), (losses, norms) = jax.lax.scan(
        step, (state.params, state.m, state.v, state.t),
        jax.random.split(k_local, local_steps))
    val = jax.vmap(functools.partial(_accuracy, forward, mm))(
        p, sw.val_x, sw.val_y)
    a = kmeans(k_kmeans, _features(p), k, iters)
    a = brain_storm(k_bso, a, val, k, p1, p2)
    p = _eq2(p, a, sw.n_samples, k)
    return State(p, m, v, t, next_key), RoundOut(
        losses[-1], val, a, {path: n[0] for path, n in norms.items()})
