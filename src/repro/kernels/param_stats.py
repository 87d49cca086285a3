"""param_stats: streaming moment reduction over parameter tensors.

This is the paper's §III.B distribution-summarisation step as a TPU
kernel: a pure memory-bound reduction over up to billions of elements,
tiled (rows, 128) into VMEM, accumulating partial sums across the
sequential grid.

Two numerics/throughput properties beyond the naive version:

* **Shifted accumulation.** The kernel accumulates sum(x - shift) and
  sum((x - shift)^2) with shift = the mean of the first block, so the
  wrapper's ``E[d^2] - E[d]^2`` does not catastrophically cancel when
  ``mean^2 >> var`` (the naive ``ss/n - mean^2`` loses half the fp32
  mantissa on large-mean tensors).

* **Client-batched entry point.** ``param_stats_batched`` reduces a
  client-stacked ``(N, ...)`` tensor on an ``(N, n_blocks)`` grid — the
  whole swarm's per-tensor stats in ONE device program instead of N
  host dispatches (the coordinator hot path of a BSO-SL round).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128

# Per-client accumulator: one (8, 128) f32 tile, so the output block is
# aligned to the TPU's (8, 128) tiling and every update is a whole-tile
# vector op (Mosaic refuses scalar stores to VMEM). Rows hold per-lane
# partial sums; the wrapper reduces the lanes:
#   row 0: sum(x - shift)   row 1: sum((x - shift)^2)   row 2: shift
_ACC_ROWS = 8


def _stats_kernel(x_ref, out_ref, *, n_blocks, n_tail, inv_first):
    i = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)            # (block_rows, LANES)
    rows, lanes = x.shape
    acc_row = jax.lax.broadcasted_iota(jnp.int32, (_ACC_ROWS, lanes), 0)

    @pl.when(i == 0)
    def _init():
        # shift = mean of the first block's real elements: zero padding
        # never perturbs the sum and inv_first normalises by the true
        # valid count, so the shift lands on the data's magnitude.
        shift = jnp.sum(x, axis=(0, 1), keepdims=True) * inv_first
        out_ref[0] = jnp.where(acc_row == 2, shift, 0.0)

    # Mask the tail padding: a padded zero would contribute (0 - shift)
    # to the shifted moments, and correcting that analytically in the
    # wrapper re-introduces the very cancellation the shift removes
    # (n_pad * shift^2 can dwarf the real sum of squares). Only the
    # final block carries padding, so mask by block-local index — a
    # global element index would overflow int32 for >=2^31-element
    # tensors, which this module explicitly serves.
    idx_local = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) * lanes
                 + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1))
    valid = (i < n_blocks - 1) | (idx_local < n_tail)
    acc = out_ref[0]                            # (_ACC_ROWS, LANES)
    shift = acc[2:3, :]                         # (1, LANES), lanes equal
    d = jnp.where(valid, x - shift, 0.0)
    sd = jnp.sum(d, axis=0, keepdims=True)      # (1, LANES) per-lane sums
    ssd = jnp.sum(d * d, axis=0, keepdims=True)
    out_ref[0] = acc + jnp.where(acc_row == 0, sd,
                                 jnp.where(acc_row == 1, ssd, 0.0))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def param_stats_batched(x, *, block_rows=256, interpret=False):
    """Per-client (mean, var) over the trailing axes of ``x`` (N, ...).

    Returns two fp32 vectors of shape (N,). One pallas_call with grid
    (N, n_blocks): the block axis is innermost, so each client's
    accumulator tile is revisited sequentially (the standard revisited-
    output reduction pattern).
    """
    N = x.shape[0]
    n = x.size // N
    if n == 0:
        # empty tensor: (nan, nan) like jnp.mean/var, never a trace crash
        nan = jnp.full((N,), jnp.nan, jnp.float32)
        return nan, nan
    # keep the input dtype end-to-end: the kernel casts per block in
    # VMEM, so a wrapper-level astype would double HBM traffic for the
    # memory-bound bf16 case
    flat = x.reshape(N, -1)
    per_block = block_rows * LANES
    n_blocks = max(1, -(-n // per_block))
    padded = n_blocks * per_block
    flat = jnp.pad(flat, ((0, 0), (0, padded - n)))
    tiles = flat.reshape(N, n_blocks * block_rows, LANES)

    kernel = functools.partial(_stats_kernel, n_blocks=n_blocks,
                               n_tail=n - (n_blocks - 1) * per_block,
                               inv_first=1.0 / min(n, per_block))
    out = pl.pallas_call(
        kernel,
        grid=(N, n_blocks),
        in_specs=[pl.BlockSpec((1, block_rows, LANES), lambda b, i: (b, i, 0))],
        out_specs=pl.BlockSpec((1, _ACC_ROWS, LANES), lambda b, i: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, _ACC_ROWS, LANES), jnp.float32),
        interpret=interpret,
    )(tiles)

    sd, ssd = out[:, 0].sum(axis=1), out[:, 1].sum(axis=1)
    shift = out[:, 2, 0]
    mean = shift + sd / n
    var = jnp.maximum(ssd / n - (sd / n) ** 2, 0.0)
    return mean, var


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def param_stats(x, *, block_rows=256, interpret=False):
    """Returns (mean, var) fp32 of any-shape floating tensor ``x``."""
    m, v = param_stats_batched(x.reshape((1,) + x.shape),
                               block_rows=block_rows, interpret=interpret)
    return m[0], v[0]
