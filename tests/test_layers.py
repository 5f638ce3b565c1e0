"""Layer-level oracles: SSD vs naive recurrence, RG-LRU vs sequential
loop, causal conv, RoPE properties, ring buffers, blocked flash vs dense."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L

f32 = jnp.float32


# --------------------------------------------------------------------------
# SSD
# --------------------------------------------------------------------------

def ssd_naive(x, dt, A, B, C):
    """Token-by-token linear recurrence oracle."""
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    Bh = np.repeat(np.asarray(B, np.float64), rep, axis=2)
    Ch = np.repeat(np.asarray(C, np.float64), rep, axis=2)
    xn = np.asarray(x, np.float64)
    dtn = np.asarray(dt, np.float64)
    An = np.asarray(A, np.float64)
    s = np.zeros((b, h, p, n))
    ys = np.zeros((b, l, h, p))
    for t in range(l):
        dA = np.exp(dtn[:, t] * An)                       # (b, h)
        s = s * dA[..., None, None] + np.einsum(
            "bhp,bhn->bhpn", xn[:, t] * dtn[:, t][..., None], Bh[:, t])
        ys[:, t] = np.einsum("bhpn,bhn->bhp", s, Ch[:, t])
    return ys, s


@pytest.mark.parametrize("chunk", [4, 8, 16])
@pytest.mark.parametrize("groups", [1, 2])
def test_ssd_scan_matches_naive(chunk, groups, rng):
    b, l, h, p, n = 2, 32, 4, 8, 16
    ks = jax.random.split(rng, 4)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, groups, n))
    C = jax.random.normal(jax.random.fold_in(rng, 9), (b, l, groups, n))
    y, final = L.ssd_scan(x, dt, A, B, C, chunk)
    y_ref, s_ref = ssd_naive(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(final), s_ref, rtol=2e-4,
                               atol=2e-4)


def test_ssd_decode_continues_scan(rng):
    b, l, h, p, n = 1, 16, 2, 4, 8
    ks = jax.random.split(rng, 4)
    x = jax.random.normal(ks[0], (b, l + 1, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l + 1, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l + 1, 1, n))
    C = jax.random.normal(jax.random.fold_in(rng, 7), (b, l + 1, 1, n))
    _, state = L.ssd_scan(x[:, :l], dt[:, :l], A, B[:, :l], C[:, :l], 8)
    new_state, y1 = L.ssd_decode_step(state, x[:, l], dt[:, l], A,
                                      B[:, l], C[:, l])
    y_full, s_full = ssd_naive(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y1), y_full[:, l], rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(new_state), s_full, rtol=1e-4,
                               atol=1e-4)


# --------------------------------------------------------------------------
# RG-LRU
# --------------------------------------------------------------------------

def test_rglru_scan_matches_loop(rng):
    b, l, d = 2, 24, 8
    ks = jax.random.split(rng, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, l, d)))
    bb = jax.random.normal(ks[1], (b, l, d))
    h0 = jax.random.normal(ks[2], (b, d))
    h, h_last = L._rglru_scan(a, bb, h0)
    s = np.asarray(h0, np.float64)
    for t in range(l):
        s = np.asarray(a[:, t]) * s + np.asarray(bb[:, t])
        np.testing.assert_allclose(np.asarray(h[:, t]), s, rtol=1e-4,
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_last), s, rtol=1e-4, atol=1e-5)


def test_causal_conv_matches_manual(rng):
    b, l, c, w = 2, 10, 3, 4
    x = jax.random.normal(rng, (b, l, c))
    wgt = jax.random.normal(jax.random.fold_in(rng, 1), (w, c))
    bias = jax.random.normal(jax.random.fold_in(rng, 2), (c,))
    y, state = L._causal_conv(x, wgt, bias)
    xp = np.concatenate([np.zeros((b, w - 1, c)), np.asarray(x)], axis=1)
    for t in range(l):
        want = (xp[:, t:t + w] * np.asarray(wgt)[None]).sum(1) + \
            np.asarray(bias)
        np.testing.assert_allclose(np.asarray(y[:, t]), want, rtol=1e-4,
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(state), xp[:, -(w - 1):],
                               atol=1e-6)


def test_causal_conv_decode_chaining(rng):
    b, l, c, w = 1, 8, 2, 4
    x = jax.random.normal(rng, (b, l, c))
    wgt = jax.random.normal(jax.random.fold_in(rng, 1), (w, c))
    bias = jnp.zeros((c,))
    y_full, _ = L._causal_conv(x, wgt, bias)
    y_steps = []
    state = jnp.zeros((b, w - 1, c))
    for t in range(l):
        y, state = L._causal_conv(x[:, t:t + 1], wgt, bias, state)
        y_steps.append(y[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(y_steps, 1)),
                               np.asarray(y_full), rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# Positions
# --------------------------------------------------------------------------

def test_rope_preserves_norm(rng):
    x = jax.random.normal(rng, (2, 8, 4, 32))
    cos, sin = L.rope_cos_sin(jnp.arange(8)[None].repeat(2, 0), 32, 1e4)
    y = L.apply_rope(x, cos, sin)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)


def test_rope_relative_property(rng):
    """<rope(q, i), rope(k, j)> depends only on i - j."""
    q = jax.random.normal(rng, (1, 1, 1, 16))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (1, 1, 1, 16))

    def dot_at(i, j):
        ci, si = L.rope_cos_sin(jnp.array([[i]]), 16, 1e4)
        cj, sj = L.rope_cos_sin(jnp.array([[j]]), 16, 1e4)
        qi = L.apply_rope(q, ci, si)
        kj = L.apply_rope(k, cj, sj)
        return float((qi * kj).sum())

    assert abs(dot_at(5, 3) - dot_at(10, 8)) < 1e-4
    assert abs(dot_at(5, 3) - dot_at(6, 3)) > 1e-5


def test_partial_rope_passthrough(rng):
    x = jax.random.normal(rng, (1, 4, 2, 32))
    cos, sin = L.rope_cos_sin(jnp.arange(4)[None], 8, 1e4)   # 25% rotary
    y = L.apply_rope(x, cos, sin, fraction=0.25)
    np.testing.assert_array_equal(np.asarray(y[..., 8:]),
                                  np.asarray(x[..., 8:]))
    assert not np.allclose(np.asarray(y[..., :8]), np.asarray(x[..., :8]))


def test_mrope_sections_rotate_by_stream(rng):
    x = jax.random.normal(rng, (1, 3, 1, 16))
    # identical position streams == standard rope
    pos3 = jnp.broadcast_to(jnp.arange(3)[None, None], (3, 1, 3))
    cm, sm = L.mrope_cos_sin(pos3, 16, 1e4, (3, 3, 2))
    cs, ss = L.rope_cos_sin(jnp.arange(3)[None], 16, 1e4)
    np.testing.assert_allclose(np.asarray(cm), np.asarray(cs), atol=1e-6)
    # different streams differ
    pos3b = pos3.at[1].add(5)
    cm2, _ = L.mrope_cos_sin(pos3b, 16, 1e4, (3, 3, 2))
    assert not np.allclose(np.asarray(cm2), np.asarray(cm))


# --------------------------------------------------------------------------
# Ring buffer
# --------------------------------------------------------------------------

def test_ring_from_full_maps_positions(rng):
    B, Lf, S = 1, 10, 4
    full = jnp.arange(Lf, dtype=f32)[None, :, None]
    ring = L.ring_from_full(full, S)
    # position p lives at slot p % S; last S positions kept
    for p in range(Lf - S, Lf):
        assert float(ring[0, p % S, 0]) == p


def test_ring_from_full_short_seq(rng):
    full = jnp.arange(3, dtype=f32)[None, :, None]
    ring = L.ring_from_full(full, 8)
    assert float(ring[0, 0, 0]) == 0 and float(ring[0, 2, 0]) == 2
    assert float(jnp.abs(ring[0, 3:]).sum()) == 0


# --------------------------------------------------------------------------
# Blocked flash (jnp)
# --------------------------------------------------------------------------

def _flash_cases():
    """(B, H, K, Lq, D, window, block_q, block_k, dtype, scale): MHA with
    and without a window at scale 0.2, then at the oracle's 1/sqrt(D) in
    f32 and bf16: MHA, GQA (H/K = 4), MQA with a window, a ragged
    sequence (200, no multiple of the block) and a single block of D =
    128."""
    shapes = [(1, 4, 4, 256, 64, 0, 64, 64), (2, 8, 2, 128, 64, 0, 64, 32),
              (1, 4, 1, 256, 64, 96, 64, 64), (2, 2, 2, 200, 32, 0, 64, 64),
              (1, 2, 2, 128, 128, 0, 128, 128)]
    cases = [pytest.param(2, 4, 4, 128, 32, w, 32, 32, f32, 0.2, id=str(w))
             for w in (0, 48)]
    for dtype in (f32, jnp.bfloat16):
        for shape in shapes:
            B, H, K, Lq, D, w = shape[:6]
            cases.append(pytest.param(
                *shape, dtype, D ** -0.5,
                id=f"B{B}H{H}K{K}L{Lq}D{D}w{w}-{jnp.dtype(dtype).name}"))
    return cases


@pytest.mark.parametrize("B,H,K,Lq,D,window,block_q,block_k,dtype,scale",
                         _flash_cases())
def test_flash_jnp_vs_dense(B, H, K, Lq, D, window, block_q, block_k, dtype,
                            scale, rng):
    """``flash_attention_jnp`` (K/V expanded to H heads, as
    ``attn_block_apply`` calls it) against the dense causal oracle of
    ``kernels/flash_attention/ref.py``, the scale folded into q."""
    from repro.kernels.flash_attention import ref as fa_ref
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, Lq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Lq, K, D), dtype)
    v = jax.random.normal(ks[2], (B, Lq, K, D), dtype)
    rep = lambda x: jnp.repeat(x, H // K, axis=2)
    out = L.flash_attention_jnp(q, rep(k), rep(v), scale=scale,
                                window=window, block_q=block_q,
                                block_k=block_k)
    heads_major = lambda x: jnp.swapaxes(x, 1, 2)
    want = heads_major(fa_ref.attention(
        heads_major(q * jnp.asarray(scale * D ** 0.5, dtype)),
        heads_major(k), heads_major(v), window=window))
    assert out.dtype == dtype
    atol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, f32), np.asarray(want, f32),
                               atol=atol)


def test_flash_jnp_first_row_attends_self_only(rng):
    B, H, Lq, D = 1, 1, 128, 32
    q = jax.random.normal(rng, (B, Lq, H, D))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, Lq, H, D))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, Lq, H, D))
    out = L.flash_attention_jnp(q, k, v, scale=D ** -0.5, block_q=64,
                                block_k=64)
    np.testing.assert_allclose(np.asarray(out[0, 0, 0]),
                               np.asarray(v[0, 0, 0]), rtol=1e-5)


def test_flash_jnp_grad_matches_dense(rng):
    B, H, Lq, D = 1, 2, 64, 16
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, Lq, H, D))
    k = jax.random.normal(ks[1], (B, Lq, H, D))
    v = jax.random.normal(ks[2], (B, Lq, H, D))

    def f_flash(q):
        return L.flash_attention_jnp(q, k, v, scale=0.25, block_q=16,
                                     block_k=16).sum()

    def f_dense(q):
        mask = L.causal_mask(Lq, Lq)[None, None, None]
        return L.attention(q, k, v, scale=0.25, mask=mask).sum()

    g1 = jax.grad(f_flash)(q)
    g2 = jax.grad(f_dense)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-3,
                               atol=1e-4)


def _attn_paths_taken(monkeypatch, cfg, seq, *, decode=False):
    """Trace one attention layer (shapes only) with the backend check
    patched to the TPU; which of the kernel and ``flash_attention_jnp``
    it calls (the dense paths call neither)."""
    taken = []

    def kernel(q, k, v, *, scale):
        taken.append("kernel")
        return jnp.zeros(q.shape[:2] + (q.shape[2] * q.shape[3],), q.dtype)

    def jnp_path(q, k, v, *, scale, window=0):
        taken.append("jnp")
        return jnp.zeros(q.shape, q.dtype)

    monkeypatch.setattr(L, "_on_tpu", lambda: True)
    monkeypatch.setattr(L, "flash_attention_train", kernel)
    monkeypatch.setattr(L, "flash_attention_jnp", jnp_path)
    params = L.attn_block_init(jax.random.PRNGKey(0), cfg)
    if decode:
        x = jnp.zeros((1, 1, cfg.d_model), cfg.dtype)
        cache = L.attn_cache_init(cfg, 1, seq, cfg.dtype)
        run = lambda p, x: L.attn_block_apply(
            p, cfg, x, positions=jnp.zeros((1, 1), jnp.int32), cache=cache,
            cache_pos=jnp.asarray(seq - 1, jnp.int32))
    else:
        x = jnp.zeros((1, seq, cfg.d_model), cfg.dtype)
        run = lambda p, x: L.attn_block_apply(
            p, cfg, x, positions=jnp.arange(seq)[None])
    jax.eval_shape(run, params, x)
    return taken


def test_attention_takes_the_kernel_only_where_its_inputs_allow(
        monkeypatch):
    """On the TPU a causal GQA layer at ``L >= FLASH_THRESHOLD`` runs the
    Pallas kernel; a window, heads split over a ``model`` axis and a
    context mesh of several devices keep ``flash_attention_jnp``; decode
    and sequences below the threshold stay on dense attention."""
    import dataclasses
    import repro.configs as C
    cfg = C.get_smoke("granite-moe-3b-a800m")
    n = L.FLASH_THRESHOLD
    assert _attn_paths_taken(monkeypatch, cfg, n) == ["kernel"]
    assert _attn_paths_taken(monkeypatch, cfg, 4 * n) == ["kernel"]
    windowed = dataclasses.replace(cfg, attn="sliding", window=256)
    assert _attn_paths_taken(monkeypatch, windowed, n) == ["jnp"]
    assert _attn_paths_taken(monkeypatch, cfg, n // 2) == []
    assert _attn_paths_taken(monkeypatch, cfg, n, decode=True) == []
    # a sequence the kernel's blocks do not divide
    assert _attn_paths_taken(monkeypatch, cfg, n + 64) == ["jnp"]
    with monkeypatch.context() as mp:
        mp.setattr(L, "constrain", lambda x, *spec: x)   # no mesh here
        L.enable_activation_sharding(True, model_n=2)
        try:
            assert _attn_paths_taken(mp, cfg, n) == ["jnp"]
        finally:
            L.enable_activation_sharding(False)
    mesh = jax.sharding.AbstractMesh((2,), ("data",))
    with jax.sharding.use_abstract_mesh(mesh):
        assert _attn_paths_taken(monkeypatch, cfg, n) == ["jnp"]


def _moe_cfg(arch="granite-moe-3b-a800m", **kw):
    import dataclasses
    import repro.configs as C
    return dataclasses.replace(C.get_smoke(arch), **kw)


def _moe_dense(params, cfg, x):
    """Every expert on every token times the token's routing weight for it
    (0 outside its top k), plus the shared experts: no sort, no dispatch."""
    xt = x.reshape(-1, cfg.d_model)
    top_l, top_e = jax.lax.top_k(xt @ params["router"], cfg.top_k)
    gates = jax.nn.softmax(top_l, axis=-1)
    y = 0.0
    for j, e in enumerate(cfg.held_experts):
        h = (jax.nn.silu(xt @ params["w_gate"][j]) * (xt @ params["w_up"][j]))
        y = y + ((gates * (top_e == e)).sum(-1)[:, None]
                 * (h @ params["w_down"][j]))
    if cfg.n_shared_experts:
        y = y + L.mlp_apply(params["shared"], "swiglu", x).reshape(xt.shape)
    return y.reshape(x.shape)


def test_moe_aux_loss_uniform_router():
    """A perfectly uniform router gives aux loss ~= 1 (Switch norm)."""
    cfg = _moe_cfg()
    key = jax.random.PRNGKey(0)
    params = L.moe_init(key, cfg)
    params["router"] = jnp.zeros_like(params["router"])
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    y, aux = L.moe_apply(params, cfg, x)
    assert y.shape == x.shape
    assert abs(float(aux["moe_aux"]) - 1.0) < 0.05


@pytest.mark.parametrize("arch", ["granite-moe-3b-a800m", "deepseek-v2-236b"])
def test_moe_whole_layer_matches_dense_loop(arch, rng):
    """Values and gradients (router included) of the sorted, grouped layer
    equal the dense per-expert loop, in f32 (only the order of sums
    differs)."""
    cfg = _moe_cfg(arch, n_experts=8, top_k=3)
    params = L.moe_init(rng, cfg)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 16, cfg.d_model))
    y, _ = L.moe_apply(params, cfg, x)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_moe_dense(params, cfg, x)),
                               rtol=1e-5, atol=1e-6)
    sq = lambda f: lambda p, x: (f(p, x) ** 2).sum()
    g_layer = jax.grad(sq(lambda p, x: L.moe_apply(p, cfg, x)[0]),
                       argnums=(0, 1))(params, x)
    g_dense = jax.grad(sq(lambda p, x: _moe_dense(p, cfg, x)),
                       argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(g_layer), jax.tree.leaves(g_dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("arch", ["granite-moe-3b-a800m", "deepseek-v2-236b"])
def test_moe_held_shares_sum_to_whole_layer(arch, rng):
    """Four layers holding disjoint quarters of the experts (router whole)
    add up to the layer that holds them all, with the shared experts that
    every share computes counted once."""
    cfg = _moe_cfg(arch, n_experts=8, top_k=3)
    params = L.moe_init(rng, cfg)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 16, cfg.d_model))
    whole, stats = L.moe_apply(params, cfg, x)
    total, rows = 0.0, 0.0
    for s in range(4):
        share = _moe_cfg(arch, n_experts=8, top_k=3, first_held_expert=2 * s,
                         n_held_experts=2)
        p = {**params, **{k: params[k][2 * s:2 * s + 2]
                          for k in ("w_gate", "w_up", "w_down")}}
        y, st = L.moe_apply(p, share, x)
        total, rows = total + y, rows + st["expert_rows_sum"]
        np.testing.assert_allclose(float(st["moe_aux"]),
                                   float(stats["moe_aux"]), rtol=1e-6)
    if cfg.n_shared_experts:
        total = total - 3 * L.mlp_apply(params["shared"], "swiglu", x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    assert float(rows) == float(stats["expert_rows_sum"]) == 32 * 3


def test_moe_drops_nothing_when_every_token_picks_one_expert(rng):
    """A router biased to send every token to expert 5: that expert
    computes all 64 tokens, and the layer still equals the dense loop."""
    cfg = _moe_cfg(n_experts=8, top_k=2)
    params = L.moe_init(rng, cfg)
    params["router"] = params["router"].at[0, 5].set(100.0)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (4, 16, cfg.d_model))
    x = x.at[..., 0].set(10.0)
    y, stats = L.moe_apply(params, cfg, x)
    assert float(stats["expert_rows_max"]) == 64
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_moe_dense(params, cfg, x)),
                               rtol=1e-5, atol=1e-6)
    # the share that holds expert 5 computes all its tokens, likewise
    share = _moe_cfg(n_experts=8, top_k=2, first_held_expert=4,
                     n_held_experts=2)
    p = {**params, **{k: params[k][4:6] for k in ("w_gate", "w_up", "w_down")}}
    y, stats = L.moe_apply(p, share, x)
    assert float(stats["expert_rows_max"]) == 64
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_moe_dense(p, share, x)),
                               rtol=1e-5, atol=1e-6)


def _held_share_cfg(**kw):
    """4 of 8 experts held, top 3: buffers of T, 2T and 3T rows."""
    return _moe_cfg(n_experts=8, top_k=3, first_held_expert=2,
                    n_held_experts=4, **kw)


def _steered(rng, cfg, lean):
    """Layer weights and one (2, 16)-token batch per worker whose feature
    0 is ``lean[w]`` and moves the logits of held experts 3-5 by it: -10
    leaves held expert 2 alone to take assignments (the smallest buffer),
    0 the plain routing, +10 sends every token's top 3 to held experts
    (the full buffer)."""
    params = L.moe_init(rng, cfg)
    params["router"] = params["router"].at[0].set(0.0).at[0, 3:6].set(1.0)
    x = jax.random.normal(jax.random.fold_in(rng, 1),
                          (len(lean), 2, 16, cfg.d_model))
    return params, x.at[..., 0].set(jnp.asarray(lean, f32)[:, None, None])


@pytest.mark.parametrize("lean,rows", [
    ((-10.0, -10.0, -10.0), (32, 32, 32)),
    ((0.0, 0.0, 0.0), (64, 64, 64)),
    ((10.0, 10.0, 10.0), (96, 96, 96)),
    ((-10.0, 0.0, 10.0), (32, 64, 96)),
], ids=["smallest", "middle", "full", "each_its_own"])
def test_moe_bucketed_layer_matches_dense_under_worker_vmap(rng, lean, rows):
    """A layer holding a share runs each worker on the smallest buffer of
    T = 32, 64 or 96 rows that holds its routed rows, and under the
    worker ``vmap`` its values and its gradients (router, experts and
    ``x``) still equal the dense loop.  The full buffer computes all 3T
    assignments: no routing drops one."""
    cfg = _held_share_cfg()
    params, x = _steered(rng, cfg, lean)
    y, st = jax.vmap(lambda xi: L.moe_apply(params, cfg, xi))(x)
    live = np.asarray(st["expert_rows_sum"])
    np.testing.assert_array_equal(np.asarray(st["expert_rows_buffer"]), rows)
    assert ((live > np.asarray(rows) - 32) & (live <= np.asarray(rows))).all()
    if rows[0] == 96:
        assert (live == 96).all()
    np.testing.assert_allclose(
        np.asarray(st["expert_rows_sum"] / st["expert_rows_buffer"]),
        live / np.asarray(rows, np.float32), rtol=1e-6)
    dense = jax.vmap(lambda xi: _moe_dense(params, cfg, xi))(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                               rtol=1e-5, atol=1e-6)
    sq = lambda f: lambda p, x: (f(p, x) ** 2).sum()
    grads = lambda f: jax.vmap(jax.grad(sq(f), argnums=(0, 1)),
                               in_axes=(None, 0))(params, x)
    g_layer = grads(lambda p, x: L.moe_apply(p, cfg, x)[0])
    g_dense = grads(lambda p, x: _moe_dense(p, cfg, x))
    for a, b in zip(jax.tree.leaves(g_layer), jax.tree.leaves(g_dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def _conds(jaxpr):
    """The ``cond`` equations of a jaxpr and of every jaxpr inside it."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            out.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _conds(inner)
    return out


def _moe_grad_jaxpr(cfg, rng):
    params, x = _steered(rng, cfg, (0.0, 0.0, 0.0))
    loss = lambda p, xi: (L.moe_apply(p, cfg, xi)[0] ** 2).sum()
    return jax.make_jaxpr(jax.vmap(jax.value_and_grad(loss, argnums=(0, 1)),
                                   in_axes=(None, 0)))(params, x).jaxpr


def test_moe_bucket_choice_is_a_branch_per_worker(rng):
    """Under the worker ``vmap`` the layer's forward and backward each
    choose their buffer with a ``cond`` of one branch per buffer (T, 2T,
    3T): each worker runs one branch, not a select of all three."""
    conds = _conds(_moe_grad_jaxpr(_held_share_cfg(), rng))
    assert len(conds) >= 2
    assert all(len(c.params["branches"]) == 3 for c in conds)


def test_moe_layer_holding_every_expert_has_no_branch(rng):
    """A layer that holds every expert computes all T * K assignments on
    one buffer, with no ``cond``."""
    cfg = _moe_cfg(n_experts=8, top_k=3)
    assert len(cfg.held_experts) == cfg.n_experts
    assert _conds(_moe_grad_jaxpr(cfg, rng)) == []
