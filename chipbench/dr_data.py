"""Synthetic diabetic-retinopathy clinics with the paper's Table-I counts.

The benchmark's own generator, so that no later change to the program
can move the traffic it measures. It follows the program's
``repro.data.dr.make_dr_swarm_data`` in kind (fundus-like fields with
grade-dependent lesions and a clinic-specific tint), but renders on the
device in one jitted call and stores 8-bit RGB, as fundus photographs
are, so that 3,657 images at 224 px take a second, not minutes.

Table I of arXiv:2404.15585 gives 3,657 APTOS images over 14 clinics
and 5 grades; each clinic is split into train/val/test by the
configuration's ``split``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# rows: grades 0..4; columns: clinics C1..C14
TABLE_I = np.array(
    [
        [2, 31, 901, 351, 0, 231, 279, 0, 0, 0, 0, 0, 0, 10],
        [13, 234, 19, 0, 13, 44, 7, 2, 13, 18, 0, 6, 1, 0],
        [307, 233, 39, 0, 91, 165, 1, 63, 28, 11, 33, 3, 22, 0],
        [32, 60, 2, 0, 6, 47, 0, 9, 1, 4, 5, 21, 3, 2],
        [56, 80, 13, 0, 31, 46, 0, 18, 19, 19, 4, 4, 2, 2],
    ],
    dtype=np.int64,
)
assert int(TABLE_I.sum()) == 3657
MAX_LESIONS = 8          # grade g draws 2g lesions
RENDER_BATCH = 128       # images rendered at once (bounds device memory)


def _render(key, grade, tint, size: int):
    """One image (size, size, 3) uint8."""
    yy, xx = jnp.mgrid[0:size, 0:size].astype(jnp.float32)
    c = (size - 1) / 2.0
    r = jnp.sqrt((yy - c) ** 2 + (xx - c) ** 2) / (size / 2.0)
    img = jnp.clip(1.0 - r, 0.0, 1.0)[..., None] * jnp.array(
        [0.55, 0.25, 0.10], jnp.float32)
    k_noise, k_lesion = jax.random.split(key)
    img += 0.12 * jax.random.normal(k_noise, (size, size, 3), jnp.float32)
    u = jax.random.uniform(k_lesion, (MAX_LESIONS, 3))
    ang = 2 * jnp.pi * u[:, 0]
    rad = (0.15 + 0.7 * u[:, 1]) * (size / 2.0)
    sigma = (0.8 + 1.4 * u[:, 2]) * size / 32.0
    on = (jnp.arange(MAX_LESIONS) < 2 * grade).astype(jnp.float32)
    ly, lx = c + rad * jnp.sin(ang), c + rad * jnp.cos(ang)
    d2 = (yy[None] - ly[:, None, None]) ** 2 + (xx[None] - lx[:, None, None]) ** 2
    blobs = jnp.sum(on[:, None, None] * jnp.exp(
        -d2 / (2 * sigma[:, None, None] ** 2)), axis=0)
    level = 0.22 + 0.06 * grade.astype(jnp.float32)
    img += blobs[..., None] * jnp.stack([level, 0.9 * level,
                                         jnp.float32(0.1)])
    img = jnp.clip(img * tint, 0.0, 1.0)
    return jnp.round(img * 255.0).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames="size")
def _render_all(key, grades, tints, size: int):
    keys = jax.vmap(functools.partial(jax.random.fold_in, key))(
        jnp.arange(grades.shape[0]))
    return jax.lax.map(lambda a: _render(*a, size), (keys, grades, tints),
                       batch_size=RENDER_BATCH)


def _split_sizes(n: int, split) -> tuple:
    """(train, val) counts of a clinic of ``n`` images; val and test get
    at least one image each where the clinic has three or more."""
    n_tr = max(int(round(split[0] * n)), 1)
    n_val = max(int(round(split[1] * n)), 1)
    n_tr = min(n_tr, max(n - 2, 1))
    return n_tr, min(n_val, max(n - n_tr - 1, 1))


def make_clinics(table: np.ndarray, image_size: int, seed: int,
                 split=(0.8, 0.1, 0.1)) -> list:
    """One dict per clinic (column of ``table``):
    ``{"train": (X, y), "val": (X, y), "test": (X, y), "n_train": int}``,
    X uint8 (n, H, W, 3), y int32 (n,). The same seed gives the same
    images, on any backend."""
    if len(split) != 3 or abs(sum(split) - 1.0) > 1e-9:
        raise ValueError(f"split {split} is not three shares of 1")
    seed %= 2**64
    rng = np.random.default_rng(seed)
    grades, clinic_of = [], []
    for c in range(table.shape[1]):
        for g in range(table.shape[0]):
            grades += [g] * int(table[g, c])
            clinic_of += [c] * int(table[g, c])
    grades, clinic_of = np.asarray(grades, np.int32), np.asarray(clinic_of)
    tints = np.stack([np.random.default_rng(1000 + c).uniform(0.95, 1.05, 3)
                      for c in range(table.shape[1])]).astype(np.float32)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    X_all = np.asarray(_render_all(key, jnp.asarray(grades),
                                   jnp.asarray(tints[clinic_of]),
                                   image_size))
    clinics = []
    for c in range(table.shape[1]):
        idx = np.flatnonzero(clinic_of == c)
        idx = idx[rng.permutation(len(idx))]
        X, y = X_all[idx], grades[idx]
        n_tr, n_val = _split_sizes(len(y), split)
        splits = {"train": (X[:n_tr], y[:n_tr]),
                  "val": (X[n_tr:n_tr + n_val], y[n_tr:n_tr + n_val]),
                  "test": (X[n_tr + n_val:], y[n_tr + n_val:])}
        # tiny clinics: non-empty val/test by reusing the last images
        for k in ("val", "test"):
            if len(splits[k][1]) == 0:
                splits[k] = (X[-2:], y[-2:])
        clinics.append({**splits, "n_train": n_tr})
    return clinics


def clinic_table(config: dict) -> np.ndarray:
    """The Table-I columns a configuration keeps (its first ``clinics``)."""
    if config["table"] != "table1":
        raise ValueError(f"unknown clinic table {config['table']!r}")
    return TABLE_I[:, :config["clinics"]]
