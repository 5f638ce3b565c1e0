"""Pure-jnp oracle for the safeguard pairwise-distance kernel."""

import jax
import jax.numpy as jnp


@jax.jit
def gram(a):
    """(m, d) -> (m, m) float32 Gram matrix as plain f32 products and f32
    sums (jitted, so XLA fuses the product into the reduction instead of
    materializing ``(m, m, d)``).  Not a matmul: on a
    TPU v5e an XLA f32 dot of this skinny shape drifts with d (at
    ``precision=HIGHEST`` its error reached 1e-2 of ``|a|^2 + |b|^2`` at
    d = 2.2e8), and the distances cancel exactly those terms."""
    af = a.astype(jnp.float32)
    return jnp.sum(af[:, None, :] * af[None, :, :], axis=-1)


def pairwise_sqdist(a):
    """(m, d) -> (m, m) float32 squared L2 distances, clipped at 0."""
    g = gram(a)
    diag = jnp.diagonal(g)
    return jnp.maximum(diag[:, None] + diag[None, :] - 2.0 * g, 0.0)


def fused_accumulate_sqdist(acc, g, reset, scale):
    """Oracle for the fused safeguard update: windowed accumulate-and-reset
    followed by pairwise distances of the updated accumulators."""
    new = jnp.where(jnp.asarray(reset, bool), jnp.zeros_like(acc),
                    acc).astype(jnp.float32) \
        + g.astype(jnp.float32) * jnp.float32(scale)
    return new, pairwise_sqdist(new)
