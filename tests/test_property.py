"""Hypothesis property tests on the system's invariants.

Skips cleanly (instead of crashing collection) when ``hypothesis`` is not
installed — it is an optional dev dependency, not a runtime one."""

import pytest

hypothesis = pytest.importorskip("hypothesis")
hnp = pytest.importorskip("hypothesis.extra.numpy")
st = pytest.importorskip("hypothesis.strategies")

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core import SafeguardConfig, init_state, safeguard_step
from repro.core import aggregators as agg
from repro.core import attacks as atk
from repro.core import defenses as dfn
from repro.core import tree_utils as tu
from repro.core import sketch as sk

SET = dict(deadline=None, max_examples=25,
           suppress_health_check=[hypothesis.HealthCheck.too_slow])

finite = st.floats(-10, 10, allow_nan=False, width=32)


def stacks(m_min=4, m_max=12, d_max=8):
    return hnp.arrays(np.float32,
                      st.tuples(st.integers(m_min, m_max),
                                st.integers(1, d_max),
                                st.integers(1, d_max)),
                      elements=finite)


@given(stacks())
@settings(**SET)
def test_gram_matches_numpy(arr):
    g = {"x": jnp.asarray(arr)}
    gram = np.asarray(tu.tree_gram(g))
    flat = arr.reshape(arr.shape[0], -1).astype(np.float64)
    np.testing.assert_allclose(gram, flat @ flat.T, rtol=1e-3, atol=1e-3)


@given(stacks())
@settings(**SET)
def test_sqdist_nonneg_symmetric_zero_diag(arr):
    d = np.asarray(tu.tree_pairwise_sqdist({"x": jnp.asarray(arr)}))
    assert (d >= 0).all()
    np.testing.assert_allclose(d, d.T, atol=1e-3)
    np.testing.assert_allclose(np.diagonal(d), 0.0, atol=1e-3)


@given(stacks(), st.integers(0, 1000))
@settings(**SET)
def test_coord_median_bounded_and_permutation_invariant(arr, seed):
    g = {"x": jnp.asarray(arr)}
    med = np.asarray(agg.coordinate_median(g)["x"])
    assert (med >= arr.min(axis=0) - 1e-6).all()
    assert (med <= arr.max(axis=0) + 1e-6).all()
    perm = np.random.RandomState(seed).permutation(arr.shape[0])
    med2 = np.asarray(agg.coordinate_median({"x": jnp.asarray(arr[perm])})["x"])
    np.testing.assert_allclose(med, med2, atol=1e-6)


@given(stacks(m_min=6))
@settings(**SET)
def test_trimmed_mean_bounded(arr):
    out = np.asarray(agg.trimmed_mean({"x": jnp.asarray(arr)}, trim=1)["x"])
    s = np.sort(arr, axis=0)
    assert (out >= s[1] - 1e-5).all() and (out <= s[-2] + 1e-5).all()


@given(stacks(m_min=6), st.integers(1, 2))
@settings(**SET)
def test_krum_returns_a_worker(arr, b):
    g = {"x": jnp.asarray(arr)}
    out = np.asarray(agg.krum(g, n_byz=b)["x"])
    assert any(np.allclose(out, arr[i], atol=1e-6)
               for i in range(arr.shape[0]))


@given(st.integers(4, 12), st.integers(0, 5), st.integers(0, 2 ** 31 - 1))
@settings(**SET)
def test_honest_execution_never_evicts(m, steps_extra, seed):
    """Concentration guarantee (Lemma 3.2) at test scale: with threshold
    floor above the noise level, no honest worker is ever evicted."""
    key = jax.random.PRNGKey(seed)
    cfg = SafeguardConfig(m=m, T0=8, T1=24, threshold_floor=1.0)
    params = {"w": jnp.zeros((6, 3))}
    stt = init_state(cfg, params)
    for t in range(10 + steps_extra):
        key, k = jax.random.split(key)
        g = {"w": 1.0 + 0.05 * jax.random.normal(k, (m, 6, 3))}
        stt, _, _ = safeguard_step(stt, g, cfg)
    assert bool(stt.good.all())


@given(st.integers(0, 2 ** 31 - 1))
@settings(**SET)
def test_safeguard_permutation_equivariance(seed):
    """Relabeling workers permutes the good-mask identically."""
    m = 8
    key = jax.random.PRNGKey(seed)
    perm = jax.random.permutation(jax.random.fold_in(key, 1), m)
    cfg = SafeguardConfig(m=m, T0=8, T1=16, threshold_floor=0.3)
    byz = jnp.arange(m) < 3

    def run(order):
        stt = init_state(cfg, {"w": jnp.zeros((5,))})
        kk = key
        for t in range(20):
            kk, k = jax.random.split(kk)
            g = {"w": 1.0 + 0.05 * jax.random.normal(k, (m, 5))}
            g, _ = atk.attack_sign_flip(g, byz, None, jnp.int32(t), k)
            g = {"w": g["w"][order]}
            stt, _, _ = safeguard_step(stt, g, cfg)
        return stt.good

    base = run(jnp.arange(m))
    permuted = run(perm)
    np.testing.assert_array_equal(np.asarray(base)[np.asarray(perm)],
                                  np.asarray(permuted))


@given(st.floats(1e2, 1e5), st.integers(2, 10),
       st.integers(0, 2 ** 31 - 1))
@settings(**SET)
def test_near_duplicate_rows_never_nan_any_sqdist_path(mag, m, seed):
    """NaN regression (ISSUE 3): near-duplicate large-magnitude rows make
    ``diag_i + diag_j - 2 G_ij`` cancel below zero in f32; every sqdist
    producer must clamp at 0 so ``sqrt`` never sees a negative — a NaN
    distance compares False against the threshold and silently evicts
    honest workers."""
    d = 256
    key = jax.random.PRNGKey(seed)
    base = mag * jax.random.normal(key, (1, d))
    rows = base + 1e-6 * mag * jax.random.normal(
        jax.random.fold_in(key, 1), (m, d))
    from repro.kernels.safeguard_filter import (fused_accumulate_sqdist,
                                                pairwise_sqdist,
                                                sqdist_from_tile_grams)
    from repro.kernels.safeguard_filter import ref as sf_ref
    outs = {
        "pallas": pairwise_sqdist(rows),
        "ref": sf_ref.pairwise_sqdist(rows),
        "tree": tu.tree_pairwise_sqdist({"x": rows}),
        "fused": sqdist_from_tile_grams(fused_accumulate_sqdist(
            [rows], (0,), (jnp.zeros_like(rows),), 0, 1.0,
            align=128)[1][0]),
        "sketch": sk.sketch_pairwise_sqdist(
            sk.sketch_tree({"x": rows}, k=128, reps=2)),
    }
    for name, sq in outs.items():
        sq = np.asarray(sq)
        assert np.isfinite(sq).all(), name
        assert (sq >= 0).all(), name
        assert np.isfinite(np.sqrt(sq)).all(), name


@given(hnp.arrays(np.float32, st.tuples(st.integers(2, 6),
                                        st.integers(64, 256)),
                  elements=finite))
@settings(**SET)
def test_sketch_preserves_distance_ordering(arr):
    """JL property (statistical): sketched distances approximate exact
    distances within generous relative error for well-separated pairs."""
    g = {"x": jnp.asarray(arr)}
    exact = np.asarray(tu.tree_pairwise_sqdist(g))
    sks = sk.sketch_tree(g, k=1024, reps=4, seed=0)
    approx = np.asarray(sk.sketch_pairwise_sqdist(sks))
    m = arr.shape[0]
    for i in range(m):
        for j in range(m):
            if exact[i, j] > 1e-3:
                assert abs(approx[i, j] - exact[i, j]) < 0.5 * exact[i, j] \
                    + 1e-2


@given(st.integers(1, 40), st.integers(2, 30))
@settings(**SET)
def test_ring_from_full_property(L, S):
    from repro.models import layers
    full = jnp.arange(L, dtype=jnp.float32)[None, :, None]
    ring = np.asarray(layers.ring_from_full(full, S))[0, :, 0]
    for p in range(max(0, L - S), L):
        assert ring[p % S] == p


# ------------------------------------------------- Defense protocol zoo

# Distance passes exercised for the safeguard-family defenses: the Pallas
# Gram kernel (interpret mode on CPU) and the sharded-mesh XLA dot path,
# which the step takes when its accumulators are pinned to a sharding.
_SG_BACKENDS = ("gram", "xla")


def _registry_for(m, n_byz):
    return dfn.make_registry(m, n_byz, T0=4, T1=8, threshold_floor=0.5)


def _sg_ctx(backend):
    """The defense ctx that puts the safeguard on ``backend``'s pass."""
    if backend == "gram":
        return {}
    mesh = jax.sharding.Mesh(jax.devices()[:1], ("data",))
    return {"acc_sharding": jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec())}


def _normal_stack(m, d, seed):
    """Tie-free random stack (continuous normals: permutation argmin/argsort
    tie-breaks are measure-zero, unlike hypothesis's raw float arrays)."""
    return jax.random.normal(jax.random.PRNGKey(seed), (m, d))


def _clustered_stack(m, d, seed, outliers=2):
    """Tight honest cluster + far outlier rows: every *eviction margin* is
    wide.  The empirical filter's median is 'any worker satisfying ...'
    (paper Alg 1) — when two workers share the k-th order-statistic
    distance EXACTLY (the same symmetric edge), argmin tie-breaks are
    index-order-dependent by spec, so equivariance of the good mask is
    only meaningful when the tie cannot flip a decision."""
    base = 1.0 + 0.05 * jax.random.normal(jax.random.PRNGKey(seed), (m, d))
    return base.at[:outliers].add(5.0)


def _run_steps(d, mat, perm=None, steps=2, backend="gram"):
    """Run ``steps`` aggregations (state warms up), permuting the worker
    rows of every input by ``perm``; the safeguard on ``backend``."""
    state = (d.init_state({"w": jnp.zeros((mat.shape[1],))})
             if d.init_state else None)
    ctx = _sg_ctx(backend)
    for t in range(steps):
        g = mat + 0.1 * t
        if perm is not None:
            g = g[perm]
        if d.needs_held_batch:
            scores = -jnp.sum(g.astype(jnp.float32) ** 2, axis=1)
            ctx = {**ctx, "scores": scores}
        agg_out, state, info = d.aggregate(state, {"w": g}, ctx)
    return agg_out, info


@given(st.integers(5, 10), st.integers(0, 2 ** 31 - 1),
       st.integers(0, 2 ** 31 - 1))
@settings(**{**SET, "max_examples": 10})   # interpreted Pallas dominates
def test_every_registry_defense_permutation_equivariant(m, seed, pseed):
    """Satellite: relabeling workers permutes the good mask and leaves the
    aggregate unchanged, for EVERY defense of the protocol registry (the
    safeguard family across both distance backends)."""
    perm = np.random.RandomState(pseed).permutation(m)
    mat = _clustered_stack(m, 6, seed)
    # n_byz=1 keeps Krum's neighborhood k = m - b - 2 >= 2: at k = 1
    # mutual nearest neighbors tie EXACTLY (the same symmetric distance),
    # and argmin tie-breaks are index-order-dependent by construction
    reg = _registry_for(m, 1)
    seen = set()
    for backend in _SG_BACKENDS:
        for name, d in reg.items():
            if name in seen and not name.startswith("safeguard"):
                continue
            seen.add(name)
            agg_base, info_base = _run_steps(d, mat, backend=backend)
            agg_perm, info_perm = _run_steps(d, mat, perm=perm,
                                             backend=backend)
            np.testing.assert_allclose(
                np.asarray(agg_base["w"]), np.asarray(agg_perm["w"]),
                rtol=2e-4, atol=2e-5, err_msg=name)
            np.testing.assert_array_equal(
                np.asarray(info_base["good"])[perm],
                np.asarray(info_perm["good"]), err_msg=name)


# Defenses with a bounded-influence guarantee against a single Byzantine
# row (mean is excluded by definition; weiszfeld's smoothed iterate is
# bounded but we assert the exact-median forms only).
_ROBUST = ("coord_median", "trimmed_mean", "geo_median", "krum", "zeno",
           "safeguard_single", "safeguard_double", "centered_clip",
           "norm_filter", "dnc", "safeguard_cclip")


@given(st.integers(6, 10), st.integers(0, 2 ** 31 - 1),
       st.floats(1e2, 1e6))
@settings(**{**SET, "max_examples": 15})
def test_robust_defenses_bound_single_byzantine_row(m, seed, mag):
    """Satellite: one colluder at magnitude ``mag`` moves a robust
    defense's aggregate by O(honest scale), never O(mag) — across both
    safeguard backends."""
    mat = _normal_stack(m, 6, seed)
    adv = mat.at[0].set(mag)
    reg = _registry_for(m, 1)
    for backend in _SG_BACKENDS:
        for name in _ROBUST:
            agg_clean, _ = _run_steps(reg[name], mat, backend=backend)
            agg_adv, _ = _run_steps(reg[name], adv, backend=backend)
            shift = float(jnp.linalg.norm(agg_adv["w"] - agg_clean["w"]))
            honest = float(jnp.linalg.norm(mat[1:], axis=1).max())
            assert np.isfinite(shift), (name, backend)
            assert shift <= 20.0 * honest + 1.0, (name, backend, shift)


@given(st.integers(2, 10), st.integers(0, 2 ** 31 - 1))
@settings(**SET)
def test_variance_attack_within_population_variance(m_half, seed):
    """The attack stays statistically plausible: byzantine coords lie
    within [mu - 3 sigma, mu + 3 sigma] of the honest population."""
    m = 2 * m_half
    key = jax.random.PRNGKey(seed)
    g = {"w": jax.random.normal(key, (m, 16))}
    byz = jnp.arange(m) < m_half // 2 + 1
    out, _ = atk.make_variance_attack(0.3)(g, byz, None, jnp.int32(0), key)
    gw = np.asarray(g["w"])[~np.asarray(byz)]
    mu, sd = gw.mean(0), gw.std(0) + 1e-9
    adv = np.asarray(out["w"])[0]
    assert (np.abs(adv - mu) <= 3.0 * sd + 1e-5).all()


# ---------------------------------------------------- hetero partitioner


@given(st.integers(1, 4), st.integers(1, 8), st.integers(0, 1000),
       st.floats(0.01, 50.0, allow_nan=False))
@settings(**SET)
def test_dirichlet_partitioner_exact_shapes(mm, per, seed, alpha):
    """Satellite (DESIGN.md §13): every worker shard has exactly B/m
    examples — sampling is with replacement against static quotas, so
    shapes never depend on how skewed the mixture is."""
    from repro.data import hetero as H
    m = 2 * mm                       # even m, s=2-compatible
    B = m * per
    key = jax.random.PRNGKey(seed)
    w = H.worker_mixtures(H.mixture_key(seed), alpha, m, 10)
    assert w.shape == (m, 10)
    np.testing.assert_allclose(np.asarray(w.sum(axis=1)), 1.0, atol=1e-5)
    labels = jax.random.randint(key, (B,), 0, 10)
    idx = H.dirichlet_indices(key, labels, w, m, per)
    assert idx.shape == (m, per) and idx.dtype == jnp.int32
    assert bool(((idx >= 0) & (idx < B)).all())


@given(st.integers(0, 1000), st.floats(0.05, 50.0, allow_nan=False),
       st.integers(2, 10))
@settings(deadline=None, max_examples=10)
def test_dirichlet_mixtures_preserve_global_marginal(seed, alpha, C):
    """E[pi_i] is uniform for the symmetric Dirichlet, so averaging the
    selection reweighting over workers preserves the pool's label
    marginal in expectation."""
    from repro.data import hetero as H
    w = H.worker_mixtures(H.mixture_key(seed), alpha, 800, C)
    np.testing.assert_allclose(np.asarray(w).mean(axis=0), 1.0 / C,
                               atol=0.08)


@given(st.integers(0, 200), st.integers(1, 5))
@settings(deadline=None, max_examples=10)
def test_dirichlet_one_hot_mixture_gives_pure_class_shards(seed, per):
    """A worker whose mixture is a one-hot on class c receives only
    class-c examples (whenever the pool contains that class)."""
    from repro.data import hetero as H
    C = 6
    key = jax.random.PRNGKey(seed)
    labels = jnp.concatenate([jnp.arange(C),                # all present
                              jax.random.randint(key, (3 * C,), 0, C)])
    w = jnp.eye(C, dtype=jnp.float32)                       # worker i = class i
    idx = H.dirichlet_indices(key, labels, w, C, per)
    picked = np.asarray(labels)[np.asarray(idx)]            # (C, per)
    np.testing.assert_array_equal(picked, np.arange(C)[:, None]
                                  * np.ones((1, per), int))


@given(st.integers(0, 500), st.integers(1, 5), st.integers(1, 4))
@settings(deadline=None, max_examples=10)
def test_dirichlet_alpha_inf_recovers_iid_split_bitexact(seed, mm, perm):
    """alpha -> inf (and alpha <= 0) recover the contiguous IID
    worker_split bit-for-bit — the sentinel and the Dirichlet limit
    agree, so IID campaign cells are unchanged by the hetero machinery."""
    from repro.data import hetero as H
    from repro.data import tasks
    from repro.data.pipeline import worker_split
    m, per = 2 * mm, 2 * perm
    task = tasks.make_teacher_task(d_in=6, d_hidden=8, n_classes=5)
    key = jax.random.fold_in(jax.random.PRNGKey(seed ^ 0xDA7A), 0)
    iid = worker_split(tasks.teacher_batch(task, key, m * per), m)
    for alpha in (float("inf"), 0.0, -3.0):
        w = H.worker_mixtures(H.mixture_key(seed), alpha, m, 5)
        got = H.hetero_worker_batch(task, key, m * per, m,
                                    mode="dirichlet", weights=w,
                                    alpha=alpha)
        assert np.array_equal(np.asarray(got["x"]), np.asarray(iid["x"]))
        assert np.array_equal(np.asarray(got["y"]), np.asarray(iid["y"]))


# ------------------------------------------------ planted-saddle family


_saddle_kind = st.sampled_from(["saddle_quad", "saddle_chain"])
_gap = st.floats(0.05, 3.0, allow_nan=False, width=32)


@given(_saddle_kind, _gap, st.integers(0, 2 ** 31 - 1))
@settings(**SET)
def test_saddle_analytic_grad_matches_autodiff(kind, gap, seed):
    """The closed-form gradient is exactly jax.grad of the closed-form
    value, to f32 tolerance, across the whole (kind, gap, x) family."""
    from repro.data import saddle as sad
    task = sad.make_saddle_task(10, kind, seed=seed % 7)
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(seed), (10,))
    want = jax.grad(lambda z: sad.saddle_value(task, z, gap))(x)
    np.testing.assert_allclose(np.asarray(sad.saddle_grad(task, x, gap)),
                               np.asarray(want), rtol=2e-4, atol=2e-5)


@given(_saddle_kind, _gap, st.integers(0, 2 ** 31 - 1))
@settings(**SET)
def test_saddle_min_eig_proxy_brackets_planted_minimum(kind, gap, seed):
    """At the planted saddle the Rayleigh proxy equals lambda_min = -gap
    exactly; everywhere it stays >= -gap (quartic curvature only adds)."""
    from repro.data import saddle as sad
    task = sad.make_saddle_task(10, kind, seed=seed % 5)
    at_saddle = float(sad.min_eig_proxy(task, sad.x_init(task)["x"], gap))
    assert at_saddle == pytest.approx(-gap, rel=1e-5)
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(seed), (10,))
    assert float(sad.min_eig_proxy(task, x, gap)) >= -gap - 1e-5 * gap


@given(_saddle_kind, _gap, st.integers(0, 2 ** 31 - 1),
       st.integers(0, 2 ** 8 - 1))
@settings(**SET)
def test_saddle_escaped_invariant_under_symmetry(kind, gap, seed, bits):
    """The escape predicate is invariant under the family's symmetry
    group: any subset of per-stage reflections u_j -> -u_j plus any
    translation in the bulk complement."""
    from repro.data import saddle as sad
    task = sad.make_saddle_task(10, kind, seed=seed % 5)
    x = 1.5 * jax.random.normal(jax.random.PRNGKey(seed), (10,))
    u = task.dirs @ x
    signs = jnp.asarray([1.0 if (bits >> j) & 1 else -1.0
                         for j in range(task.k)], jnp.float32)
    reflected = x + task.dirs.T @ ((signs - 1.0) * u)
    v = jax.random.normal(jax.random.PRNGKey(seed ^ 0xB11C), (10,))
    v = v - task.dirs.T @ (task.dirs @ v)            # bulk component
    moved = reflected + 2.0 * v
    assert bool(sad.escaped(task, moved, gap)) == \
        bool(sad.escaped(task, x, gap))


@given(st.integers(0, 2 ** 16 - 1), st.integers(1, 4), st.integers(1, 4))
@settings(deadline=None, max_examples=10)
def test_saddle_noise_zero_mean_over_seeds(seed0, mm, per):
    """IID linear-noise model: worker noise has zero mean over seeds, so
    E[g_i] is the analytic gradient (SVRG's control variate cancels it
    exactly under anchoring)."""
    from repro.data import saddle as sad
    task = sad.make_saddle_task(6, "saddle_quad")
    m = 2 * mm
    total = np.zeros((6,))
    n = 200
    for s in range(n):
        b = sad.saddle_batch(task, sad.step_key(seed0 + s, 0),
                             m * per, m)
        total += np.asarray(b["eps"]).mean(axis=(0, 1))
    assert np.abs(total / n).max() < 5.0 / np.sqrt(n * m * per)


@given(stacks(m_min=4), st.integers(0, 2 ** 16 - 1))
@settings(**SET)
def test_zeta_sq_matches_numpy(arr, mask_bits):
    """tree_dissimilarity == mean_i||g_i - mean_mask||^2 over the mask."""
    from repro.data import hetero as H
    m = arr.shape[0]
    mask = np.array([(mask_bits >> i) & 1 for i in range(m)], dtype=bool)
    if not mask.any():
        mask[0] = True
    g = {"x": jnp.asarray(arr)}
    got = float(H.zeta_sq(g, jnp.asarray(mask)))
    flat = arr.reshape(m, -1).astype(np.float64)
    center = flat[mask].mean(axis=0)
    want = float(((flat[mask] - center) ** 2).sum(axis=1).mean())
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
