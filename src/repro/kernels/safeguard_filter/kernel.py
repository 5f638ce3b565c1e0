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
  * ``fused_accumulate_sqdist_kernel`` — the safeguard hot path, one
    gradient leaf per call: each of the leaf's d-tiles applies the
    windowed accumulate-and-reset update ``acc <- [reset ? 0 : acc] + g /
    n_good`` to one or two accumulators *in place*
    (``input_output_aliases``) and forms each updated tile's Gram, so the
    O(m d) state is streamed exactly once per step.

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


def sqdist_from_tile_grams(partial):
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
        name="pairwise_sqdist_kernel",
    )(a)
    return sqdist_from_tile_grams(partial)


def _accumulate_kernel(flags_ref, scale_ref, g_ref, *refs, size: int,
                       block_d: int):
    """One d-tile of one gradient leaf: ``new = [reset ? 0 : acc] + g *
    scale`` for each accumulator, in place, and each updated tile's Gram.
    Columns past the leaf's ``size`` (the last tile's tail: the layout's
    zero gap, or whatever a partial block read) keep the accumulator's
    values."""
    n = len(refs) // 3
    accs, news, grams = refs[:n], refs[n:2 * n], refs[2 * n:]
    col = (pl.program_id(0) * block_d
           + jax.lax.broadcasted_iota(jnp.int32, (1, block_d), 1))
    inside = col < size
    step = jnp.where(inside, g_ref[...].astype(jnp.float32), 0.0) \
        * scale_ref[0]
    for k in range(n):
        a = accs[k][...]
        # select, NOT multiply-by-(1-reset): a Byzantine inf/NaN in the old
        # accumulator must be zeroed by the window reset (inf * 0 = NaN)
        new = jnp.where(flags_ref[k] != 0, jnp.zeros_like(a), a) + step
        new = jnp.where(inside, new, a)
        news[k][...] = new
        grams[k][0] = _gram_tile(new)


@functools.partial(jax.jit,
                   static_argnames=("offset", "block_d", "interpret"))
def fused_accumulate_sqdist_kernel(g, accs, resets, scale, *, offset: int,
                                   block_d: int, interpret: bool = True):
    """One gradient leaf's streamed pass of the safeguard update
    (DESIGN.md §6), for one or two accumulators at once.

    g: (m, size) in the leaf's own dtype; accs: tuple of (m, d_pad) f32
    buffers holding the leaf at columns ``offset:offset + size``, with
    ``offset`` a multiple of ``block_d`` and the leaf's last tile inside
    the buffer; resets: (len(accs),) int32 window-reset flags; scale: (1,)
    f32 (= 1 / n_good).  The grid runs over the leaf's tiles; the last is
    masked in the kernel, so the gradient is never padded.

    Returns ``(new_accs, tile_grams)``: each new accumulator aliases its
    input buffer (columns outside the leaf's tiles are not touched) and
    each ``tile_grams`` entry is the ``(n_tiles, m, m)`` f32 Grams of the
    updated tiles, to be summed by :func:`sqdist_from_tile_grams`.
    """
    m, size = g.shape
    n = len(accs)
    assert offset % block_d == 0, (offset, block_d)
    nt = pl.cdiv(size, block_d)
    base = offset // block_d
    for a in accs:
        assert a.shape[0] == m and a.dtype == jnp.float32, (a.shape, a.dtype)
        assert (base + nt) * block_d <= a.shape[1], (offset, size, a.shape)
    tile = pl.BlockSpec((m, block_d), lambda i: (0, base + i))
    out = pl.pallas_call(
        functools.partial(_accumulate_kernel, size=size, block_d=block_d),
        grid=(nt,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),      # resets
                  pl.BlockSpec(memory_space=pltpu.SMEM),      # scale
                  pl.BlockSpec((m, block_d), lambda i: (0, i))]  # grad
        + [tile] * n,                                         # accumulators
        out_specs=[tile] * n
        + [pl.BlockSpec((1, m, m), lambda i: (i, 0, 0))] * n,  # tile Grams
        out_shape=[jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in accs]
        + [jax.ShapeDtypeStruct((nt, m, m), jnp.float32)] * n,
        input_output_aliases={3 + k: k for k in range(n)},
        interpret=interpret,
        name="fused_accumulate_sqdist_kernel",
    )(resets, scale, g, *accs)
    return tuple(out[:n]), tuple(out[n:])
