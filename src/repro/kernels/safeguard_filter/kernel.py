"""Pallas kernels: blocked Gram / pairwise-distance pass over the flat
per-worker accumulator buffer (DESIGN.md §5, §6).

The safeguard filter needs all pairwise distances between m worker
accumulators of dimension d (d = model size, up to tens of billions).
Distances reduce to the Gram matrix, which is a rank-d update streamed
through VMEM:

    grid over d-tiles; each step loads an (m, bd) tile of the flat
    accumulator (HBM -> VMEM), forms its (m, m) Gram from exact f32
    products and writes it out; the per-tile Grams are summed and the
    diagonal expanded to squared distances outside the kernel.

Two entry points:

  * ``pairwise_sqdist_kernel`` — distances of an existing buffer;
  * ``fused_accumulate_sqdist_kernel`` — the safeguard hot path: each
    d-tile additionally applies the windowed accumulate-and-reset update
    ``acc <- [reset ? 0 : acc] + g / n_good`` *in place*
    (``input_output_aliases``) before forming its Gram, so the O(m d)
    state is streamed exactly once per step.

Every block spans all m worker rows (no sublane padding, see ``ops.py``);
``block_d`` is a multiple of the 128-wide lane dimension so each tile is
lane-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gram_tile(a):
    """(m, bd) f32 -> (m, m) f32 ``a @ a.T`` from exact f32 products and
    f32 lane sums, one column per worker — the oracle's arithmetic, with
    no dependence on the MXU's f32 pass configuration.  At m = 4 a tile
    costs the same as an MXU dot (both are bound by its load)."""
    cols = [jnp.sum(a * a[i:i + 1, :], axis=1, keepdims=True)
            for i in range(a.shape[0])]
    return jnp.concatenate(cols, axis=1)


def _sqdist_from_tile_grams(partial):
    """(nd, m, m) per-tile Gram partials -> (m, m) f32 squared distances.

    The partials are summed here, by XLA's tree reduction, and not into
    one VMEM accumulator inside the kernel: a running f32 sum over the
    ~4e5 tiles of a 2.2e8-wide buffer drifted by 1.4e-3 of
    ``|a|^2 + |b|^2`` on a TPU v5e — the very terms the distance cancels.
    """
    g = partial.sum(axis=0)
    diag = jnp.diagonal(g)
    return jnp.maximum(diag[:, None] + diag[None, :] - 2.0 * g, 0.0)


def _gram_kernel(a_ref, out_ref):
    out_ref[0] = _gram_tile(a_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def pairwise_sqdist_kernel(a, *, block_d: int = 512,
                           interpret: bool = True):
    """a: (m, d) with d divisible by block_d.  Returns (m, m) f32."""
    m, d = a.shape
    assert d % block_d == 0, (d, block_d)
    nd = d // block_d
    partial = pl.pallas_call(
        _gram_kernel,
        grid=(nd,),
        in_specs=[pl.BlockSpec((m, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, m, m), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nd, m, m), jnp.float32),
        interpret=interpret,
    )(a)
    return _sqdist_from_tile_grams(partial)


def _fused_kernel(reset_ref, scale_ref, acc_ref, g_ref, newacc_ref,
                  out_ref):
    # select, NOT multiply-by-(1-reset): a Byzantine inf/NaN in the old
    # accumulator must be zeroed by the window reset (inf * 0 = NaN)
    a = acc_ref[...].astype(jnp.float32)
    a = jnp.where(reset_ref[0] != 0, jnp.zeros_like(a), a)
    new = a + g_ref[...].astype(jnp.float32) * scale_ref[0]
    newacc_ref[...] = new
    out_ref[0] = _gram_tile(new)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_accumulate_sqdist_kernel(acc, g, reset, scale, *,
                                   block_d: int = 512,
                                   interpret: bool = True):
    """One streamed pass of the safeguard update (DESIGN.md §6).

    acc, g: (m, d) f32 with d divisible by block_d; reset: (1,) int32;
    scale: (1,) f32 (= 1 / n_good).  Returns (new_acc, sqdist) where
    new_acc aliases acc's buffer and sqdist is the (m, m) f32 pairwise
    squared-distance matrix of the UPDATED accumulators.
    """
    m, d = acc.shape
    assert g.shape == (m, d), (acc.shape, g.shape)
    assert d % block_d == 0, (d, block_d)
    nd = d // block_d
    new, partial = pl.pallas_call(
        _fused_kernel,
        grid=(nd,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # reset
            pl.BlockSpec(memory_space=pltpu.SMEM),            # scale
            pl.BlockSpec((m, block_d), lambda i: (0, i)),     # acc tile
            pl.BlockSpec((m, block_d), lambda i: (0, i)),     # grad tile
        ],
        out_specs=[
            pl.BlockSpec((m, block_d), lambda i: (0, i)),     # new acc
            pl.BlockSpec((1, m, m), lambda i: (i, 0, 0)),     # tile Gram
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, d), jnp.float32),
            jax.ShapeDtypeStruct((nd, m, m), jnp.float32),
        ],
        input_output_aliases={2: 0},
        interpret=interpret,
    )(reset, scale, acc, g)
    return new, _sqdist_from_tile_grams(partial)
