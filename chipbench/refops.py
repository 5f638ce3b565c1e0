"""Operations shared by the plain references: matrix products at a stated
precision, the next-token loss, the seeded normal weights.

``precision="f32"`` is the reference: float32 operands, ``HIGHEST``
(on a TPU a float32 product otherwise runs in bfloat16 passes).
``precision="fp8"`` is the control of the correctness check: each
operand of every product is rounded to float8 e4m3 under a per-tensor
scale, as an 8-bit path of the program would store it, and the products
then run as above.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

f32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
_FP8 = jnp.float8_e4m3fn
_FP8_MAX = 448.0


def fp8_round(x):
    """``x`` rounded to float8 e4m3 under a per-tensor scale.  The rounding
    is the forward pass's alone: the gradient passes straight through (a
    cast's own transpose would round the cotangents to float8 as well)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, _FP8_MAX / amax, 1.0)
    q = (x * scale).astype(_FP8).astype(f32) / scale
    return x + jax.lax.stop_gradient(q - x)


def einsum(precision: str):
    """``einsum(subscripts, a, b)`` with both operands at ``precision``."""
    def op(subscripts, a, b):
        a, b = a.astype(f32), b.astype(f32)
        if precision == "fp8":
            a, b = fp8_round(a), fp8_round(b)
        elif precision != "f32":
            raise ValueError(f"unknown precision {precision!r}")
        return jnp.einsum(subscripts, a, b, precision=HIGHEST)
    return op


def next_token_loss(logits, tokens, *, half: bool = False):
    """Mean cross-entropy of ``logits[:, t]`` against ``tokens[:, t + 1]``.

    ``half`` keeps only the first half of the positions: the "half of the
    batch left out" fault of the correctness check."""
    logits = logits[:, :-1].astype(f32)
    target = tokens[:, 1:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, target[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if half:
        nll = nll[:, : nll.shape[1] // 2]
    return nll.mean()


def normal(key, shape, dtype, std: float = 0.02):
    return (std * jax.random.normal(key, shape)).astype(dtype)


def dtype_of(model: dict):
    return jnp.dtype(model["torch_dtype"])
