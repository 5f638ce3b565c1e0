"""Jit-able wrappers for the safeguard flat-buffer kernels: handle ragged
d (zero-pad to a tile multiple — zeros do not change distances or the
accumulate) and the d-tile choice.  Under the CPU interpreter the
emulator's per-grid-step cost (not VMEM) is the overhead, so the wrappers
run ONE whole-row block; compiled TPU runs get the widest tile (up to
32768 columns) that divides d and fits VMEM.

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


# VMEM for one kernel's double-buffered (m, bd) f32 tiles: the fused
# kernel streams three (acc, g, new), well inside v5e's 16 MiB scoped VMEM
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


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_accumulate_sqdist(acc, g, reset, scale, *, block_d=None,
                            interpret: bool = True):
    """Fused safeguard update: ``new = [reset ? 0 : acc] + g * scale`` and
    the (m, m) pairwise squared distances of ``new``, in one streamed pass
    (each d-tile of the accumulator goes HBM -> VMEM exactly once).

    acc, g: (m, d) f32 — the flat-buffer layout already pads d to the
    tile multiple, so no copy is made on the hot path.  reset: ()
    bool/int.  scale: () float.

    Returns (new_acc (m, d) f32, sqdist (m, m) f32).
    """
    m, d = acc.shape
    bd = _pick_block(d + (-d) % _LANE, m, block_d, interpret)
    pad_d = (-d) % bd                    # pad to a tile multiple
    if pad_d:
        acc = jnp.pad(acc, ((0, 0), (0, pad_d)))
        g = jnp.pad(g, ((0, 0), (0, pad_d)))
    reset1 = jnp.asarray(reset, jnp.int32).reshape((1,))
    scale1 = jnp.asarray(scale, jnp.float32).reshape((1,))
    new, sq = fused_accumulate_sqdist_kernel(
        acc.astype(jnp.float32), g.astype(jnp.float32), reset1, scale1,
        block_d=bd, interpret=interpret)
    if pad_d:
        new = new[:, :d]
    return new, sq
