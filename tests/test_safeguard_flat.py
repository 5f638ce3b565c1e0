"""Flat-buffer safeguard engine (DESIGN.md §6) equivalence suite: the
flat engine must reproduce the stacked-pytree reference bit-for-bit in
its *decisions* (eviction masks, eviction times, medians) and match the
aggregate numerically, across mode x rule x reset-period x distance
pass; plus the rule that picks the pass, layout round-trips and the
fused-kernel oracle."""

import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import SafeguardConfig, init_state, safeguard_step
from repro.core import attacks as atk
from repro.core import safeguard as sg
from repro.kernels.safeguard_filter import (fused_accumulate_sqdist,
                                            sqdist_from_tile_grams)
from repro.kernels.safeguard_filter import ref as sf_ref

M = 10
PARAMS = {"w": jnp.zeros((20, 5)), "b": jnp.zeros((5,)),
          "blocks": {"h": jnp.zeros((3, 4, 2))}}


def honest_grads(key, mu=1.0, sigma=0.05):
    ks = jax.random.split(key, len(jax.tree_util.tree_leaves(PARAMS)))
    ks = iter(list(ks))
    return jax.tree.map(
        lambda p: mu + sigma * jax.random.normal(next(ks), (M,) + p.shape),
        PARAMS)


# The safeguard's state representations and distance passes, each reached
# as the step reaches it: ``gram`` is the flat engine off the TPU, ``fused``
# its state laid out as on a TPU (the kernel still interpreted here),
# ``xla`` its accumulators pinned to a sharding.
ENGINE_GRID = ["stacked", "gram", "xla", "fused"]


def _one_device():
    return NamedSharding(Mesh(jax.devices()[:1], ("data",)), P())


def path_cfg(path, **kwargs):
    return SafeguardConfig(engine="stacked" if path == "stacked" else "flat",
                           **kwargs)


def path_state(cfg, path, params=PARAMS):
    """``init_state`` on the platform ``path`` needs: a TPU for ``fused``."""
    with mock.patch.object(sg, "_fused_platform", lambda: path == "fused"):
        return init_state(cfg, params)


def path_step(cfg, path):
    shd = _one_device() if path == "xla" else None
    return jax.jit(lambda s, g: safeguard_step(s, g, cfg, acc_sharding=shd))


def run(cfg, attack_fn, byz_mask, steps, seed=0, path="gram"):
    st = path_state(cfg, path)
    key = jax.random.PRNGKey(seed)
    astate = None
    step = path_step(cfg, path)
    agg = None
    for t in range(steps):
        key, k = jax.random.split(key)
        g = honest_grads(k)
        g, astate = attack_fn(g, byz_mask, astate, jnp.int32(t), k)
        st, agg, info = step(st, g)
    return st, agg, info


@pytest.mark.parametrize("mode", ["double", "single"])
@pytest.mark.parametrize("rule", ["empirical", "theoretical"])
def test_flat_matches_stacked_decisions(mode, rule):
    byz = jnp.arange(M) < 4
    kwargs = dict(m=M, T0=20, T1=60, mode=mode, rule=rule)
    if rule == "empirical":
        kwargs["threshold_floor"] = 0.5
    else:
        t0, t1 = SafeguardConfig.theoretical_thresholds(20, 60, M, V=0.2)
        kwargs.update(thresh0=t0, thresh1=t1)
    outs = {}
    for path in ENGINE_GRID:
        outs[path] = run(path_cfg(path, **kwargs), atk.attack_sign_flip,
                         byz, 60, path=path)

    ref_st, ref_agg, ref_info = outs["stacked"]
    assert bool((~ref_st.good[:4]).all()), "attack must be caught"
    for key, (st, agg, info) in outs.items():
        np.testing.assert_array_equal(np.asarray(st.good),
                                      np.asarray(ref_st.good), err_msg=str(key))
        np.testing.assert_array_equal(np.asarray(st.evicted_at),
                                      np.asarray(ref_st.evicted_at),
                                      err_msg=str(key))
        assert int(info["med_B"]) == int(ref_info["med_B"]), key
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5), agg, ref_agg)


def test_flat_matches_stacked_with_reset_period():
    byz = jnp.arange(M) < 3
    attack = atk.make_burst(start=0, length=10, burst_scale=5.0)
    outs = {}
    for path in ENGINE_GRID:
        cfg = path_cfg(path, m=M, T0=10, T1=20, threshold_floor=0.5,
                       reset_period=30)
        outs[path], _, _ = run(cfg, attack, byz, 35, path=path)
    ref = outs["stacked"]
    assert bool(ref.good.all()), "reset must restore workers"
    for key, st in outs.items():
        np.testing.assert_array_equal(np.asarray(st.good),
                                      np.asarray(ref.good), err_msg=str(key))


@pytest.mark.parametrize("mag", [1e2, 1e4])
def test_sqdist_producers_clamp_at_zero(mag, rng):
    """NaN regression, producer level (deterministic twin of the
    hypothesis property test): near-duplicate large-magnitude rows push
    ``diag_i + diag_j - 2 G_ij`` into f32 cancellation; every sqdist
    producer must clamp at 0 so the filter's ``sqrt`` never sees a
    negative."""
    from repro.core import sketch as sk
    from repro.core import tree_utils as tu
    from repro.kernels.safeguard_filter import pairwise_sqdist
    m, d = 8, 256
    k1, k2 = jax.random.split(rng)
    rows = (mag * jax.random.normal(k1, (1, d))
            + 1e-6 * mag * jax.random.normal(k2, (m, d)))
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


def test_near_duplicate_grads_no_nan_and_identical_decisions():
    """NaN regression through the full safeguard step: near-duplicate
    large-magnitude gradients drive the accumulator rows into the f32
    cancellation regime on every distance pass (and the sketched path); no
    distance may go NaN, no honest worker may be evicted, and all
    passes must agree on the decisions bit-for-bit.

    The threshold floor sits well above the f32 cancellation noise
    (distances here are ~pure rounding error, a few units at mu=1e3):
    pre-clamp, a negative sqdist turns into a NaN distance that compares
    False against ANY threshold and silently evicts — which is exactly
    what this test locks out."""
    byz = jnp.zeros((M,), bool)

    def near_dup_grads(key):
        ks = iter(list(jax.random.split(
            key, len(jax.tree_util.tree_leaves(PARAMS)))))
        return jax.tree.map(
            lambda p: 1e3 * (1.0 + 1e-6 * jax.random.normal(
                next(ks), (M,) + p.shape)), PARAMS)

    outs = {}
    for path in ENGINE_GRID + ["sketch"]:
        kwargs = dict(m=M, T0=20, T1=60, threshold_floor=100.0)
        if path == "sketch":
            cfg = SafeguardConfig(use_sketch=True, sketch_k=512,
                                  sketch_reps=4, **kwargs)
        else:
            cfg = path_cfg(path, **kwargs)
        st = path_state(cfg, path)
        key = jax.random.PRNGKey(0)
        step = path_step(cfg, path)
        for t in range(10):
            key, k = jax.random.split(key)
            st, agg, info = step(st, near_dup_grads(k))
            assert bool(jnp.isfinite(info["dist_to_med_B"]).all()), \
                (path, t)
            assert bool(jnp.isfinite(info["threshold_B"])), path
        assert bool(st.good.all()), path
        for leaf in jax.tree_util.tree_leaves(agg):
            assert bool(jnp.isfinite(leaf).all()), path
        outs[path] = np.asarray(st.good)
    ref = outs["stacked"]
    for k, good in outs.items():
        np.testing.assert_array_equal(good, ref, err_msg=str(k))


def test_flat_accumulator_equals_stacked_accumulator():
    """The buffer itself (not just decisions) matches: unflattening the
    flat accumulator row reproduces the stacked accumulator leaf."""
    byz = jnp.zeros((M,), bool)
    cfg_f = SafeguardConfig(m=M, T0=50, T1=100, threshold_floor=0.5)
    cfg_s = SafeguardConfig(m=M, T0=50, T1=100, threshold_floor=0.5,
                            engine="stacked")
    st_f, _, _ = run(cfg_f, atk.attack_none, byz, 7)
    st_s, _, _ = run(cfg_s, atk.attack_none, byz, 7)
    for i in (0, M - 1):
        row = sg.unflatten_row(st_f.B[i], st_f.layout)
        stacked_i = jax.tree.map(lambda l: l[i], st_s.B)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5), row, stacked_i)


def test_sketched_state_unaffected_by_engine_flag():
    """use_sketch wins over the engine choice and carries no layout."""
    byz = jnp.arange(M) < 4
    goods = []
    for engine in ("flat", "stacked"):
        cfg = SafeguardConfig(m=M, T0=20, T1=60, threshold_floor=0.5,
                              use_sketch=True, sketch_k=512, sketch_reps=4,
                              engine=engine)
        st, _, _ = run(cfg, atk.attack_sign_flip, byz, 60)
        assert st.layout is None
        assert st.B.shape == (M, 4 * 512)
        goods.append(np.asarray(st.good))
    np.testing.assert_array_equal(goods[0], goods[1])


def test_layout_static_and_round_trip():
    lay = sg.make_layout(PARAMS)
    assert lay.d == sum(l.size for l in jax.tree_util.tree_leaves(PARAMS))
    assert lay.d_padded % 128 == 0 and lay.d_padded >= lay.d
    assert hash(lay) == hash(sg.make_layout(PARAMS))   # jit-cache friendly
    g = honest_grads(jax.random.PRNGKey(3))
    flat = sg.flatten_stacked(g, lay)
    assert flat.shape == (M, lay.d_padded)
    back = sg.unflatten_row(flat[4], lay)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b[4]), atol=1e-6), back, g)


def test_leaf_aligned_layout_round_trip_and_row_norms():
    """The layout of f32 accumulators on a TPU starts every leaf on a tile
    boundary: rows round-trip through ``unflatten_row``, the gap columns
    stay zero, and per-leaf row norms of ``B`` read by ``offsets``/``sizes``
    slices equal the stacked engine's per-leaf norms after one step."""
    cfg = dict(m=M, T0=20, T1=60, threshold_floor=0.5)
    fused = path_cfg("fused", **cfg)
    st_f = path_state(fused, "fused")
    lay = st_f.layout
    tile = sg._pad_multiple(lay.d)
    assert lay.aligned and not sg.make_layout(PARAMS).aligned
    assert lay.sizes == sg.make_layout(PARAMS).sizes
    assert all(off % tile == 0 for off in lay.offsets)
    assert lay.d_padded % tile == 0
    assert lay.d_padded >= lay.offsets[-1] + lay.sizes[-1]
    g = honest_grads(jax.random.PRNGKey(3))
    flat = sg.flatten_stacked(g, lay)
    assert flat.shape == (M, lay.d_padded)
    back = sg.unflatten_row(flat[4], lay)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b[4])), back, g)

    stacked = SafeguardConfig(engine="stacked", **cfg)
    st_f, _, _ = safeguard_step(st_f, g, fused)
    st_s, _, _ = safeguard_step(init_state(stacked, PARAMS), g, stacked)
    assert st_f.layout == lay
    B = np.asarray(st_f.B)
    covered = np.zeros(lay.d_padded, bool)
    for off, size in zip(lay.offsets, lay.sizes):
        covered[off:off + size] = True
    assert not B[:, ~covered].any()
    norms = [np.sqrt((B[:, o:o + s] ** 2).sum(axis=1))
             for o, s in zip(lay.offsets, lay.sizes)]
    want = [np.sqrt((np.asarray(leaf).reshape(M, -1) ** 2).sum(axis=1))
            for leaf in jax.tree_util.tree_leaves(st_s.B)]
    np.testing.assert_allclose(norms, want, rtol=1e-6)


def _aligned(m, shapes, key, dtype=jnp.float32):
    """A leaf-aligned layout of leaves of ``shapes``, and random
    worker-stacked gradients and accumulators on it (zero gap columns, as
    the layout keeps them)."""
    params = {f"p{i}": jnp.zeros(s) for i, s in enumerate(shapes)}
    lay = path_state(SafeguardConfig(m=m), "fused", params).layout
    ks = jax.random.split(key, 3 * len(shapes))
    stack = lambda k0: jax.tree.map(
        lambda p, k: jax.random.normal(k, (m,) + p.shape), params,
        dict(zip(params, ks[k0::3])))
    g = jax.tree.map(lambda x: x.astype(dtype), stack(0))
    accs = (sg.flatten_stacked(stack(1), lay), sg.flatten_stacked(stack(2),
                                                                   lay))
    return lay, g, accs


def _fused(lay, g, accs, resets, scale):
    new, grams = fused_accumulate_sqdist(
        jax.tree_util.tree_leaves(g), lay.offsets, accs, jnp.asarray(resets),
        scale, align=sg._pad_multiple(lay.d))
    return new, [sqdist_from_tile_grams(t) for t in grams]


@pytest.mark.parametrize("m,d", [(10, 777), (8, 1024), (3, 50)])
@pytest.mark.parametrize("reset", [0, 1])
def test_fused_kernel_matches_oracle(m, d, reset, rng):
    """A and B, under each of the four combinations of their reset flags
    (``reset`` is A's, B's takes both values), against the oracle on the
    whole buffer; the gradient is read in bf16, as the step passes it."""
    lay, g, accs = _aligned(m, [(d,), (7, 3)], rng, jnp.bfloat16)
    gflat = sg.flatten_stacked(g, lay)
    for reset_B in (0, 1):
        new, sq = _fused(lay, g, accs, [reset, reset_B], 0.125)
        for acc, r, got, got_sq in zip(accs, (reset, reset_B), new, sq):
            ref_new, ref_sq = sf_ref.fused_accumulate_sqdist(acc, gflat, r,
                                                             0.125)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref_new),
                                       atol=1e-5)
            np.testing.assert_allclose(np.asarray(got_sq),
                                       np.asarray(ref_sq),
                                       atol=1e-3 * max(d, 1))


def test_fused_kernel_reset_zeroes_nonfinite_accumulator(rng):
    """The window reset must be a select, not multiply-by-(1-reset): a
    Byzantine inf/NaN in the old accumulator has to vanish at the reset
    (inf * 0 = NaN would poison distances forever)."""
    lay, g, (A, B) = _aligned(8, [(256,)], rng)
    A = A.at[2].set(jnp.inf).at[3].set(jnp.nan)
    B = B.at[1].set(jnp.nan).at[5].set(-jnp.inf)
    new, sq = _fused(lay, g, (A, B), [1, 1], 0.5)
    gflat = sg.flatten_stacked(g, lay)
    for acc, got, got_sq in zip((A, B), new, sq):
        ref_new, ref_sq = sf_ref.fused_accumulate_sqdist(acc, gflat, 1, 0.5)
        assert bool(jnp.isfinite(got).all()) and \
            bool(jnp.isfinite(got_sq).all())
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref_new))
        np.testing.assert_allclose(np.asarray(got_sq), np.asarray(ref_sq),
                                   atol=1e-3)


@pytest.mark.parametrize("mode", ["double", "single"])
def test_fused_kernel_explicit_block_not_dividing(mode, rng):
    """A leaf whose size is no tile multiple: the kernel masks its last
    tile, so the leaf's columns match the oracle and every other column
    (the gap after it, the other leaves') keeps its bits, here random
    values; ``single`` runs the same kernel on B alone."""
    m, size, off, align = 8, 1000, 1024, 512
    k1, k2, k3 = jax.random.split(rng, 3)
    g = jax.random.normal(k1, (m, 10, 100))
    accs = (jax.random.normal(k2, (m, 3072)),
            jax.random.normal(k3, (m, 3072)))
    accs = accs if mode == "double" else accs[1:]
    new, grams = fused_accumulate_sqdist([g], (off,), accs,
                                         jnp.zeros(len(accs), bool), 0.25,
                                         align=align)
    assert [t.shape for t in grams] == [(2, m, m)] * len(accs)
    for acc, got, t in zip(accs, new, grams):
        got, acc = np.asarray(got), np.asarray(acc)
        ref_new, _ = sf_ref.fused_accumulate_sqdist(
            acc[:, off:off + size], g.reshape(m, -1), 0, 0.25)
        np.testing.assert_allclose(got[:, off:off + size],
                                   np.asarray(ref_new), atol=1e-5)
        np.testing.assert_array_equal(got[:, :off], acc[:, :off])
        np.testing.assert_array_equal(got[:, off + size:],
                                      acc[:, off + size:])
        # the Grams are those of the leaf's two whole tiles
        tiles = got[:, off:off + 2 * align]
        np.testing.assert_allclose(np.asarray(t.sum(axis=0)),
                                   tiles @ tiles.T, rtol=1e-5, atol=1e-2)


def test_flat_state_shapes_and_dtype():
    cfg = SafeguardConfig(m=M, T0=20, T1=60, threshold_floor=0.5,
                          acc_dtype=jnp.bfloat16)
    st = init_state(cfg, PARAMS)
    assert st.B.shape == (M, st.layout.d_padded)
    assert st.B.dtype == jnp.bfloat16
    # bf16 accumulators take the Gram kernel, on a TPU too, and still run
    g = honest_grads(jax.random.PRNGKey(0))
    st2, _, _ = jax.jit(lambda s, gr: safeguard_step(s, gr, cfg))(st, g)
    assert st2.B.dtype == jnp.bfloat16


@pytest.mark.parametrize("tpu,dtype,pinned,kernel", [
    (False, jnp.float32, False, "pairwise_sqdist_kernel"),
    (True, jnp.float32, False, "fused_accumulate_sqdist_kernel"),
    (True, jnp.bfloat16, False, "pairwise_sqdist_kernel"),
    (True, jnp.float32, True, None),
    (False, jnp.float32, True, None),
], ids=["cpu-f32", "tpu-f32", "tpu-bf16", "tpu-f32-sharded",
        "cpu-f32-sharded"])
def test_step_picks_its_distance_pass(tpu, dtype, pinned, kernel):
    """The flat step's distance pass follows from what it observes: the
    XLA multiply-reduce (no Pallas kernel) for accumulators pinned to a
    sharding, else the per-leaf accumulate+Gram kernel where the state was
    laid out on a TPU for f32, else the Gram kernel."""
    cfg = SafeguardConfig(m=M, T0=20, T1=60, acc_dtype=dtype)
    with mock.patch.object(sg, "_fused_platform", lambda: tpu):
        st = init_state(cfg, PARAMS)
    shd = _one_device() if pinned else None
    jaxpr = jax.make_jaxpr(
        lambda s, g: safeguard_step(s, g, cfg, acc_sharding=shd))(
            st, honest_grads(jax.random.PRNGKey(0)))
    kernels = set(re.findall(r"\b(\w+_kernel)\b", str(jaxpr)))
    assert kernels == ({kernel} if kernel else set())
