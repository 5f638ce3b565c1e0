"""Jit-able wrappers for the safeguard flat-buffer kernels: the d-tile
choice, and for the Gram pass the padding of a ragged d (zeros do not
change distances).  Under the CPU interpreter the emulator's per-grid-step
cost (not VMEM) is the overhead, so ``pairwise_sqdist`` runs ONE whole-row
block; compiled TPU runs get the widest tile (up to 32768 columns) that
divides d and fits VMEM.  ``fused_accumulate_sqdist`` runs one kernel call
per gradient leaf on tiles of the layout's leaf alignment.

The worker rows are never padded: every block spans all m rows, which
Mosaic accepts for any m (a block dim equal to the array dim).  Padding
them to the sublane multiple would copy the whole (m, d) f32 buffer — at
TinyLlama-1.1B widths, 2 layers and m = 4 that copy alone is 6.5 GiB,
enough to push the step past a 16 GB chip."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.safeguard_filter.kernel import (
    fused_accumulate_sqdist_kernel, pairwise_sqdist_kernel)

_LANE = 128


# VMEM for one kernel's double-buffered (m, bd) tiles: the accumulate
# kernel streams five (g, A, B, new A, new B), inside v5e's 16 MiB scoped
# VMEM
_VMEM_TILE_BUDGET = 8 * 2**20


def _pick_block(d: int, m: int, block_d, interpret: bool) -> int:
    """Largest lane-aligned tile up to 32768 that divides d and whose three
    double-buffered (m, bd) f32 tiles fit the VMEM budget; the whole row
    when interpreting."""
    if block_d is not None:
        return min(block_d, d)
    if interpret:
        return d
    rows = m + (-m) % 8                       # VMEM pads rows to sublanes
    bd = 32768
    while bd > _LANE and (d % bd or 6 * rows * bd * 4 > _VMEM_TILE_BUDGET):
        bd //= 2
    return bd if d % bd == 0 else d


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def pairwise_sqdist(a, *, block_d: int = 512, interpret: bool = True):
    """a: (m, d) any dtype -> (m, m) f32 squared distances.

    ``block_d=None`` picks the tile automatically (one whole-row block
    under the interpreter)."""
    m, d = a.shape
    if block_d is None:
        bd = _pick_block(d if interpret else d + (-d) % _LANE, m, None,
                         interpret)
    else:
        bd = min(block_d, max(_LANE, _LANE * ((d + _LANE - 1) // _LANE)))
    pad_d = (-d) % bd
    if pad_d:
        a = jnp.pad(a, ((0, 0), (0, pad_d)))
    return pairwise_sqdist_kernel(a, block_d=bd, interpret=interpret)


def _leaf_block(size: int, align: int, m: int, n_acc: int,
                interpret: bool) -> int:
    """d-tile of one leaf's accumulate pass: the layout's leaf alignment
    ``align`` (a power of two, so every tile is lane-aligned and no tile
    straddles two leaves), halved until the gradient tile and the ``n_acc``
    accumulators' input and output tiles, double-buffered, fit the VMEM
    budget, and until it is no wider than the leaf needs."""
    bd = align
    if not interpret:
        rows = m + (-m) % 8                   # VMEM pads rows to sublanes
        while bd > _LANE and (2 * (2 * n_acc + 1) * rows * bd * 4
                              > _VMEM_TILE_BUDGET):
            bd //= 2
    while bd > _LANE and bd // 2 >= size:
        bd //= 2
    return bd


@functools.partial(jax.jit,
                   static_argnames=("offsets", "align", "interpret"))
def fused_accumulate_sqdist(leaves, offsets, accs, resets, scale, *,
                            align: int, interpret: bool = True):
    """The safeguard update of one or two flat accumulators in one streamed
    pass per gradient leaf: ``new = [reset ? 0 : acc] + g * scale`` over
    each leaf's columns, in place, with every updated tile's Gram.

    leaves: the worker-stacked gradient leaves ``(m, ...)``, any float
    dtype, read as they are (no f32 copy, no padding).  offsets: each
    leaf's first column, a multiple of ``align``.  accs: tuple of
    ``(m, d_pad)`` f32 buffers.  resets: one window-reset flag per
    accumulator.  scale: () float.

    The kernel calls are chained on the aliased accumulators with nothing
    between them, so each buffer is updated in place.  Returns
    ``(new_accs, tile_grams)``: ``tile_grams[k]`` holds accumulator k's
    per-tile ``(m, m)`` Grams of all leaves, for ``sqdist_from_tile_grams``.
    """
    m = accs[0].shape[0]
    n = len(accs)
    resets = jnp.asarray(resets, jnp.int32).reshape((n,))
    scale = jnp.asarray(scale, jnp.float32).reshape((1,))
    grams = [[] for _ in accs]
    for leaf, off in zip(leaves, offsets):
        g = leaf.reshape(m, -1)
        bd = _leaf_block(g.shape[1], align, m, n, interpret)
        accs, tile = fused_accumulate_sqdist_kernel(
            g, tuple(accs), resets, scale, offset=off, block_d=bd,
            interpret=interpret)
        for k in range(n):
            grams[k].append(tile[k])
    return tuple(accs), tuple(jnp.concatenate(t) for t in grams)
