"""The main path's Pallas kernels, compiled for a described TPU v5e.

No chip is attached: the TPU compiler builds each kernel for one chip of
a ``v5e:2x2`` topology described in a fixture, at the widths the system
runs (the 14-clinic SqueezeNet swarm, a large bf16 tensor, the
coordinator's k-means, granite-3-2b attention). Each test asserts that
the compiled program holds the Mosaic kernel (``tpu_custom_call``), so
a kernel that Mosaic refuses, or that slipped back to a jnp fallback,
fails here instead of on the chip. Nothing runs, so nothing numeric is
checked; interpret-mode parity lives in ``test_kernels.py``.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import OptimizerConfig
from repro.kernels import ops as kops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.kmeans_assign import kmeans_assign
from repro.kernels.param_stats import param_stats_batched
from repro.launch.comm import collective_bytes
from repro.launch.mesh import make_fleet_mesh
from repro.launch.swarm_fleet import fleet_setup
from repro.models import build_model
from repro.optim.optimizers import make_optimizer
from repro.sharding import use_sharding

N_CLINICS = 14
# granite-3-2b attention: 32 query heads, 8 KV heads, head dim 64
HEADS, KV_HEADS, HEAD_DIM = 32, 8, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_param_stats_compiles_for_the_squeezenet_swarm(one_chip):
    model = build_model(get_config("squeezenet-dr"))
    params = jax.eval_shape(
        lambda: jax.vmap(model.init)(
            jax.random.split(jax.random.PRNGKey(0), N_CLINICS)))
    leaves = [_spec(x.shape, x.dtype, one_chip)
              for x in jax.tree.leaves(params)]
    _assert_kernel(lambda ls: [param_stats_batched(x) for x in ls], leaves)


def test_param_stats_compiles_for_a_large_bf16_tensor(one_chip):
    x = _spec((N_CLINICS, 4096, 2048), jnp.bfloat16, one_chip)
    _assert_kernel(param_stats_batched, x)


@pytest.mark.parametrize("n", [N_CLINICS, 4096])
def test_kmeans_assign_compiles(one_chip, n):
    X = _spec((n, 64), jnp.float32, one_chip)
    C = _spec((3, 64), jnp.float32, one_chip)
    _assert_kernel(kmeans_assign, X, C)


@pytest.mark.parametrize("window", [0, 512])
def test_flash_attention_compiles_at_granite_width(one_chip, window):
    q = _spec((1, HEADS, 2048, HEAD_DIM), jnp.bfloat16, one_chip)
    kv = _spec((1, KV_HEADS, 2048, HEAD_DIM), jnp.bfloat16, one_chip)
    _assert_kernel(lambda q, k, v: flash_attention(q, k, v, window=window),
                   q, kv, kv)


@pytest.mark.parametrize("window", [0, 1024])
def test_flash_decode_compiles_at_granite_width(one_chip, window):
    q = _spec((8, HEADS, 1, HEAD_DIM), jnp.bfloat16, one_chip)
    kv = _spec((8, KV_HEADS, 4096, HEAD_DIM), jnp.bfloat16, one_chip)
    pos = _spec((8,), jnp.int32, one_chip)
    _assert_kernel(
        lambda q, k, v, p: flash_decode(q, k, v, p, window=window),
        q, kv, kv, pos)


def test_fleet_mesh_spans_the_chips_or_warns(topo):
    assert make_fleet_mesh(12, devices=topo.devices).shape["pod"] == 4
    with pytest.warns(RuntimeWarning, match="2 devices idle"):
        mesh = make_fleet_mesh(N_CLINICS, devices=topo.devices)
    assert mesh.shape["pod"] == 2


def test_fleet_round_compiles_on_four_chips(topo, monkeypatch):
    """The fleet round of 4 clinics on a 4-pod mesh: the stat kernel is
    Mosaic's, and Eq. 2 all-reduces across the pods."""
    # the round program picks interpret mode from the attached backend
    monkeypatch.setattr(kops, "auto_interpret", lambda: False)
    n, steps, batch, image = 4, 2, 8, 16
    model = build_model(get_config("squeezenet-dr"))
    opt = make_optimizer(OptimizerConfig(name="adam", lr=2e-3))
    mesh = make_fleet_mesh(n, devices=topo.devices)
    program = fleet_setup(model, opt, mesh, k=n, n_local_steps=steps,
                          use_pallas_stats=True, with_eval=True,
                          donate=True, spmd="shard_map")
    params = jax.eval_shape(lambda: jax.vmap(model.init)(
        jax.random.split(jax.random.PRNGKey(0), n)))
    f32, i32 = jnp.float32, jnp.int32
    args = (params, jax.eval_shape(jax.vmap(opt.init), params),
            {"images": jax.ShapeDtypeStruct(
                (n, steps * batch, image, image, 3), f32),
             "labels": jax.ShapeDtypeStruct((n, steps * batch), i32)},
            {"images": jax.ShapeDtypeStruct((n, 1, 64, image, image, 3),
                                            f32),
             "labels": jax.ShapeDtypeStruct((n, 1, 64), i32)},
            jax.ShapeDtypeStruct((), f32), jax.ShapeDtypeStruct((n,), i32),
            jax.ShapeDtypeStruct((n,), f32))
    specs = [jax.tree.map(lambda x, sh=sh: _spec(x.shape, x.dtype, sh), a)
             for a, sh in zip(args, program.in_shardings)]
    with mesh, use_sharding(mesh, program.rules):
        hlo = program.jit_fn.lower(*specs).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert collective_bytes(hlo)["all-reduce"] > 0
