"""Pallas kernel validation (interpret=True on CPU): shape/dtype sweeps
asserting allclose against each ``ref.py`` pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.safeguard_filter import pairwise_sqdist
from repro.kernels.safeguard_filter import ref as sf_ref


# --------------------------------------------------------------------------
# safeguard_filter
# --------------------------------------------------------------------------

@pytest.mark.parametrize("m,d", [(4, 128), (10, 1000), (16, 4096),
                                 (7, 513), (32, 2048), (33, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_sqdist_sweep(m, d, dtype, rng):
    a = jax.random.normal(rng, (m, d), dtype)
    out = pairwise_sqdist(a)
    want = sf_ref.pairwise_sqdist(a)
    tol = 1e-3 * d if dtype == jnp.bfloat16 else 1e-4 * d
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=tol)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(jnp.diagonal(out)), 0.0,
                               atol=tol)


def test_pairwise_sqdist_symmetry(rng):
    a = jax.random.normal(rng, (12, 777))
    out = np.asarray(pairwise_sqdist(a))
    np.testing.assert_allclose(out, out.T, atol=1e-4)


# --------------------------------------------------------------------------
# flash_attention_train (splash forward, dq and dkv kernels)
# --------------------------------------------------------------------------

def _train_attention_cases(rng, L, dtype, B=2, H=6, K=2, D=64, lead=()):
    """q, k, v of a causal GQA layer (group of 3, as Granite's 24/8) and
    a cotangent weight for the loss."""
    ks = jax.random.split(jax.random.fold_in(rng, L), 4)
    q, k, v = (jax.random.normal(kk, lead + (B, L, h, D), dtype)
               for kk, h in zip(ks, (H, K, K)))
    w = jax.random.normal(ks[3], lead + (B, L, H * D), jnp.float32)
    return q, k, v, w


def _value_and_qkv_grad(f, w):
    loss = lambda q, k, v: (f(q, k, v).astype(jnp.float32) * w).sum()
    return jax.jit(lambda q, k, v: (f(q, k, v),
                                    jax.grad(loss, (0, 1, 2))(q, k, v)))


def _train_paths(L, H, K, scale):
    """The kernel, the dense masked ``attention`` and ``flash_attention_jnp``
    (GQA expanded to MHA, as ``attn_block_apply`` calls it), each
    (B, L, H·D) from q (B, L, H, D) and k, v (B, L, K, D)."""
    from repro.kernels.flash_attention import flash_attention_train
    from repro.models import layers as ML
    mask = ML.causal_mask(L, L)[None, None, None]
    rep = lambda x: jnp.repeat(x, H // K, axis=2)
    return {
        "kernel": lambda q, k, v: flash_attention_train(
            q, k, v, scale=scale, interpret=True),
        "dense": lambda q, k, v: ML.attention(q, k, v, scale=scale,
                                              mask=mask),
        "jnp": lambda q, k, v: ML.flash_attention_jnp(
            q, rep(k), rep(v), scale=scale, block_q=128,
            block_k=128).reshape(q.shape[0], L, -1),
    }


def _rel_err(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


# largest error, over the output and the q/k/v gradients, relative to the
# f32 reference's largest magnitude: f32 rounding, and bf16 inputs and
# MXU operands (eps 2**-8; measured up to 4.6e-3 for the kernel and the
# dense path alike, and up to 7.3e-3 for flash_attention_jnp)
TRAIN_ATTN_TOL = {jnp.float32: 1e-5, jnp.bfloat16: 1.2e-2}


@pytest.mark.parametrize("L", [256, 512])
@pytest.mark.parametrize("scale", [1 / 64, 1 / 8], ids=["1/64", "1/sqrtD"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_train_matches_dense_and_jnp(L, scale, dtype, rng):
    """Output and gradients of q, k and v against the dense masked
    attention in f32 (on the same, possibly bf16-rounded, inputs) and
    against ``flash_attention_jnp`` in the same dtype."""
    H, K = 6, 2
    q, k, v, w = _train_attention_cases(rng, L, dtype, H=H, K=K)
    paths = _train_paths(L, H, K, scale)
    run = lambda name, *a: _value_and_qkv_grad(paths[name], w)(*a)
    out, grads = run("kernel", q, k, v)
    assert out.dtype == dtype and out.shape == (2, L, H * 64)
    assert [g.dtype for g in grads] == [dtype] * 3
    ref_out, ref_grads = run("dense", *(x.astype(jnp.float32)
                                        for x in (q, k, v)))
    jnp_out, jnp_grads = run("jnp", q, k, v)
    tol = TRAIN_ATTN_TOL[dtype]
    for got, want, other in zip((out,) + grads, (ref_out,) + ref_grads,
                                (jnp_out,) + jnp_grads):
        assert _rel_err(got, want) < tol
        assert _rel_err(got, other) < 2 * tol


def test_flash_attention_train_under_worker_vmap(rng):
    """Under a ``vmap`` over workers, as the train step takes its
    per-worker gradients, each worker gets its own attention and
    gradients: those of the same worker run alone."""
    H, K, L = 6, 2, 256
    q, k, v, w = _train_attention_cases(rng, L, jnp.float32, H=H, K=K,
                                        lead=(3,))
    kernel = _train_paths(L, H, K, 1 / 64)["kernel"]
    loss = lambda q, k, v, w: (kernel(q, k, v) * w).sum()
    grads = jax.jit(jax.vmap(jax.grad(loss, (0, 1, 2))))(q, k, v, w)
    for i in range(3):
        alone = jax.grad(loss, (0, 1, 2))(q[i], k[i], v[i], w[i])
        for got, want in zip(grads, alone):
            assert _rel_err(got[i], want) < TRAIN_ATTN_TOL[jnp.float32]
