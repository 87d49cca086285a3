"""Production mesh definitions (TPU v5e pods).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run sets XLA_FLAGS before any jax initialisation.

Every mesh is built with Auto axis types: the round programs rely on
GSPMD to partition gathers and segment-sums over pod-sharded arrays
(Eq. 2, bucketed eval, the hier round), which Explicit axes — the
``jax.make_mesh`` default since JAX 0.9 — refuse.
"""
from __future__ import annotations

import warnings

import jax
from jax.sharding import AxisType

# per-chip hardware constants (TPU v5e) used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (256 chips) single pod; (2,16,16)=512 chips multi-pod.

    Axes: pod  — swarm-client / outer-DP axis (multi-pod only)
          data — batch + FSDP axis
          model — tensor/expert-parallel axis
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_fleet_mesh(n_clients: int, devices=None):
    """Unit-scale fleet mesh: the ``pod`` (swarm-client) axis spread
    over however many local devices divide ``n_clients``; ``data`` and
    ``model`` stay size 1 (CNN-sized clients are not sharded within a
    pod). On the 8-device CPU stand-in with 8 clients this is one
    client per device — the miniature of the production (2,16,16)
    mesh's pod axis; on a single device it degrades to a trivial mesh
    so the same driver code runs under plain pytest.

    ``devices`` defaults to ``jax.devices()``. When ``n_clients`` does
    not divide across all of them the mesh takes the largest divisor
    and warns how many devices sit idle."""
    devices = jax.devices() if devices is None else list(devices)
    n_dev = len(devices)
    n_pod = max(d for d in range(1, n_dev + 1) if n_clients % d == 0)
    if n_pod < n_dev:
        warnings.warn(
            f"make_fleet_mesh: {n_clients} clients do not divide across "
            f"{n_dev} devices; using {n_pod} pods, {n_dev - n_pod} "
            "devices idle", RuntimeWarning, stacklevel=2)
    return _auto_mesh((n_pod, 1, 1), ("pod", "data", "model"),
                      devices[:n_pod])
