"""Pallas kernel: coordinate-wise robust statistics over the worker axis.

Historyless baselines (coordinate-wise median [Yin et al. 18], trimmed
mean) reduce m worker gradients coordinate-by-coordinate.  On TPU the
coordinate axis is the 128-lane dimension and the (small, <=64) worker
axis sits on sublanes, so a bitonic-style sort over sublanes vectorizes
across 128 coordinates at once:

    grid over d-tiles: load (m, bd) into VMEM, sort along the worker axis
    with a compare-exchange network (jnp.sort lowers to one), then emit
    the median / trimmed mean of the sorted tile.

One kernel serves both statistics: ``trim`` is a static parameter; the
median is the maximal trim (plus mid-pair averaging for even m).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sorted_reduce_kernel(g_ref, out_ref, *, m: int, trim: int,
                          median: bool):
    g = g_ref[...].astype(jnp.float32)          # (m, bd)
    s = jnp.sort(g, axis=0)
    if median:
        if m % 2:
            out_ref[...] = s[m // 2][None]
        else:
            out_ref[...] = (0.5 * (s[m // 2 - 1] + s[m // 2]))[None]
    else:
        kept = s[trim:m - trim]
        out_ref[...] = jnp.mean(kept, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("trim", "median", "block_d",
                                             "interpret"))
def sorted_reduce_kernel(g, *, trim: int = 0, median: bool = False,
                         block_d: int = 1024, interpret: bool = True):
    """g: (m, d), d divisible by block_d -> (d,) f32."""
    m, d = g.shape
    assert d % block_d == 0, (d, block_d)
    nd = d // block_d
    out = pl.pallas_call(
        functools.partial(_sorted_reduce_kernel, m=m, trim=trim,
                          median=median),
        grid=(nd,),
        in_specs=[pl.BlockSpec((m, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
        name="sorted_reduce_kernel",
    )(g)
    return out[0]
