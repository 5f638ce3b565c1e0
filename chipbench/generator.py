"""The one generator of token batches, driven by a ``traffic/<name>.json``
file.

Tokens follow a Zipf(``zipf_alpha``) unigram law over the configuration's
vocabulary (rank ``r`` has probability proportional to ``r**-alpha``), the
distribution of ``repro.data.pipeline.lm_batches``.  Tokens are drawn by
inverse-CDF sampling, all ``pool`` batches in one jitted call on the
device: the same seed gives the same batches, every row is its own draw,
and nothing is sampled during the measured window.  ``jax.random.PRNGKey``
takes the seed modulo 2**32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def zipf_cdf(vocab: int, alpha: float) -> np.ndarray:
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** (-alpha)
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    return cdf.astype(np.float32)


@functools.partial(jax.jit, static_argnums=(2,))
def _draw(key, cdf, shape):
    u = jax.random.uniform(key, shape, jnp.float32)
    toks = jnp.searchsorted(cdf, u, side="right").astype(jnp.int32)
    toks = jnp.minimum(toks, cdf.shape[0] - 1)
    return tuple(toks[i] for i in range(shape[0]))


def batches(traffic: dict, *, vocab: int, seed: int):
    """The pool of ``traffic["pool"]`` batches ``{"tokens": (m, b, L)}``,
    in the order the run feeds them."""
    shape = (traffic["pool"], traffic["workers"], traffic["batch_per_worker"],
             traffic["seq_len"])
    cdf = jnp.asarray(zipf_cdf(vocab, traffic["zipf_alpha"]))
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0x7AFF1C)
    return [{"tokens": t} for t in _draw(key, cdf, shape)]
