"""Baseline aggregator correctness vs numpy oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregators as agg
from repro.core import tree_utils as tu

M, D1, D2 = 9, 7, 4


@pytest.fixture
def grads(rng):
    k1, k2 = jax.random.split(rng)
    return {"a": jax.random.normal(k1, (M, D1, D2)),
            "b": jax.random.normal(k2, (M, D1))}


def flat(g):
    return np.asarray(tu.tree_stack_flatten(g))


def test_mean(grads):
    out = agg.mean(grads)
    np.testing.assert_allclose(np.asarray(out["a"]),
                               flat(grads)[:, :D1 * D2].mean(0).reshape(D1, D2),
                               rtol=1e-5)


@pytest.mark.parametrize("m,d,dtype", [(M, (D1, D2), jnp.float32)] + [
    (m, (d,), dtype) for m, d in [(5, 128), (10, 1000), (16, 4096), (9, 257),
                                  (8, 130)]
    for dtype in (jnp.float32, jnp.bfloat16)])
def test_coordinate_median(m, d, dtype, rng):
    """Odd and even m, ragged d, f32 and bf16 (the median of the f32 values,
    rounded once to the input dtype)."""
    grads = {"a": jax.random.normal(rng, (m,) + d, dtype)}
    out = agg.coordinate_median(grads)
    want = np.median(np.asarray(grads["a"], np.float32), axis=0)
    assert out["a"].dtype == dtype
    np.testing.assert_array_equal(
        np.asarray(out["a"], np.float32),
        np.asarray(jnp.asarray(want).astype(dtype), np.float32))


@pytest.mark.parametrize("m,d,trim", [(M, (D1, D2), 2), (10, (512,), 2),
                                      (16, (1000,), 4), (7, (129,), 1)])
def test_trimmed_mean(m, d, trim, rng):
    g = jax.random.normal(rng, (m,) + d)
    out = agg.trimmed_mean({"a": g}, trim=trim)
    s = np.sort(np.asarray(g), axis=0)[trim:m - trim]
    np.testing.assert_allclose(np.asarray(out["a"]), s.mean(0), atol=1e-5)


def test_trimmed_mean_rejects_overtrim(grads):
    with pytest.raises(ValueError):
        agg.trimmed_mean(grads, trim=5)


def test_geometric_medoid(grads):
    out = agg.geometric_medoid(grads)
    F = flat(grads)
    dists = np.sqrt(((F[:, None] - F[None]) ** 2).sum(-1)).sum(1)
    best = int(np.argmin(dists))
    np.testing.assert_allclose(np.asarray(out["a"]),
                               np.asarray(grads["a"][best]), atol=1e-6)


def test_weiszfeld_reduces_objective(grads):
    F = flat(grads)

    def obj(y):
        return np.sqrt(((F - y[None]) ** 2).sum(-1)).sum()

    y0 = F.mean(0)
    y = agg.geometric_median(grads, iters=32)
    y_flat = np.concatenate([np.asarray(y["a"]).ravel(),
                             np.asarray(y["b"]).ravel()])
    assert obj(y_flat) <= obj(y0) + 1e-4


def test_krum_selects_inlier():
    key = jax.random.PRNGKey(3)
    g = {"w": 0.05 * jax.random.normal(key, (M, D1))}
    # 3 byzantine workers far away
    g["w"] = g["w"].at[:3].add(50.0)
    out = agg.krum(g, n_byz=3)
    assert float(jnp.abs(out["w"]).max()) < 1.0


def test_krum_matches_bruteforce(grads):
    b = 2
    out = agg.krum(grads, n_byz=b)
    F = flat(grads)
    sq = ((F[:, None] - F[None]) ** 2).sum(-1)
    np.fill_diagonal(sq, np.inf)
    k = M - b - 2
    scores = np.sort(sq, axis=1)[:, :k].sum(1)
    best = int(np.argmin(scores))
    np.testing.assert_allclose(np.asarray(out["a"]),
                               np.asarray(grads["a"][best]), atol=1e-6)


def test_zeno_keeps_top_scores(grads):
    scores = jnp.arange(M, dtype=jnp.float32)       # worker M-1 best
    out = agg.zeno(grads, scores, n_byz=4)
    want = jax.tree.map(lambda g: g[4:].mean(0), grads)
    np.testing.assert_allclose(np.asarray(out["a"]),
                               np.asarray(want["a"]), atol=1e-5)


def test_registry(grads):
    reg = agg.make_registry(n_byz=3, m=M)
    for name, a in reg.items():
        if a.needs_scores:
            out = a.fn(grads, scores=jnp.zeros((M,)))
        else:
            out = a.fn(grads)
        assert out["a"].shape == (D1, D2), name
        assert bool(jnp.isfinite(out["a"]).all()), name
