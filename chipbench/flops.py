"""Operation counts from shapes, for the benchmark's utilization metrics.

``forward_flops`` traces a plain forward pass to a jaxpr, without
running it, and counts 2 FLOPs per multiply-add of every convolution
and matrix product in it. Elementwise work is not counted, as is usual
for model FLOPs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import Matmul


def _eqn_flops(eqn) -> int:
    name = eqn.primitive.name
    if name == "conv_general_dilated":
        rhs = eqn.invars[1].aval.shape
        dn = eqn.params["dimension_numbers"]
        out = eqn.outvars[0].aval.shape
        spatial = math.prod(rhs[d] for d in dn.rhs_spec[2:])
        cin = rhs[dn.rhs_spec[1]]
        return 2 * math.prod(out) * spatial * cin
    if name == "dot_general":
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        return 2 * math.prod(eqn.outvars[0].aval.shape) * math.prod(
            lhs[d] for d in lhs_contract)
    return 0


def _jaxpr_flops(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        total += _eqn_flops(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _jaxpr_flops(sub)
    return total


def forward_flops(forward, param_shapes: dict, image_shape: tuple) -> int:
    """Matmul and conv FLOPs of ``forward(params, images, mm)`` for
    ONE image of ``image_shape`` (H, W, C), float32 weights."""
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
                          param_shapes, is_leaf=lambda s: isinstance(s, tuple))
    images = jax.ShapeDtypeStruct((1,) + tuple(image_shape), jnp.float32)
    closed = jax.make_jaxpr(lambda p, x: forward(p, x, Matmul()))(
        params, images)
    return _jaxpr_flops(closed.jaxpr)


def param_bytes(param_shapes: dict, n_clients: int, itemsize: int = 4) -> int:
    """Bytes of the client-stacked parameters, each element read once."""
    leaves = jax.tree.leaves(param_shapes,
                             is_leaf=lambda s: isinstance(s, tuple))
    return n_clients * itemsize * sum(math.prod(s) for s in leaves)
