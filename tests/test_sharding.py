"""Sharding-rule unit tests (run on the 1-device CPU mesh by building
PartitionSpecs only — no allocation against big meshes)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import (build_param_specs,
                                  logical_axes_for_path, spec_for)


class FakeMesh:
    """Shape-only stand-in so tests can reason about 16x16 without
    building 256 devices."""
    def __init__(self, shape):
        self.shape = shape


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_logical_axes_for_known_paths():
    assert logical_axes_for_path("embedding/table", 2) == ("p_vocab", "p_embed")
    assert logical_axes_for_path("blocks/0/attn/wq", 2) == ("p_embed", "p_heads")
    assert logical_axes_for_path("blocks/3/mlp/wo", 2) == ("p_mlp", "p_embed")
    assert logical_axes_for_path("moe/experts/wi", 3) == \
        ("p_experts", "p_embed", "p_mlp")
    # stacked (scanned) variant gets a leading layers axis
    assert logical_axes_for_path("layers/period0/attn/wq", 3) == \
        ("layers", "p_embed", "p_heads")
    # adafactor factored states inherit parent axes
    assert logical_axes_for_path("v/blocks/0/mlp/wi/vr", 1) == ("p_embed",)
    assert logical_axes_for_path("v/blocks/0/mlp/wi/vc", 1) == ("p_mlp",)


def test_spec_divisibility_fallback():
    # 8 kv heads cannot shard over model=16 -> unsharded
    spec = spec_for(("p_embed", "p_kv"), MESH, (2048, 8 * 128))
    assert spec == P("data", "model")     # 1024 % 16 == 0 fine
    spec = spec_for(("p_kv",), MESH, (8,))
    assert spec == P(None)


def test_spec_never_reuses_mesh_axis():
    spec = spec_for(("cache_seq", "act_heads"), MESH, (32768, 64))
    flat = []
    for part in spec:
        if part is None:
            continue
        flat.extend(part if isinstance(part, tuple) else [part])
    assert len(flat) == len(set(flat))


def test_cache_seq_takes_both_axes_when_batch_is_one():
    # long_500k: batch 1 frees "data"; cache seq shards 256-way
    spec = spec_for(("batch", "cache_seq", "p_kv", None), MESH,
                    (1, 524288, 8, 128))
    assert spec[0] is None
    assert spec[1] == ("data", "model")


def test_build_param_specs_on_real_smoke_model():
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("granite-3-2b").smoke()
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    specs = build_param_specs(params, MESH)
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert all(isinstance(s, P) for s in flat)


def test_multipod_fsdp_uses_pod_axis():
    spec = spec_for(("p_embed", "p_mlp"), MESH3, (8192, 22528))
    # p_embed -> data then pod (8192 % (16*2) == 0)
    assert spec[0] == ("data", "pod")
    assert spec[1] == "model"


def test_shard_act_noop_without_context():
    from repro.sharding import shard_act
    x = jnp.ones((4, 8))
    y = shard_act(x, "batch", None)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_shard_act_applies_constraint_under_mesh():
    from repro.sharding import shard_act, use_sharding
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    @jax.jit
    def f(x):
        return shard_act(x, "batch", None) * 2

    with mesh, use_sharding(mesh):
        out = f(jnp.ones((4, 8)))
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((4, 8)))
