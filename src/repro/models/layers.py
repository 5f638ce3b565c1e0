"""Neural-net layers for the assigned architecture pool.

Pure functions over parameter pytrees (no flax/haiku dependency — params
are nested dicts of ``jnp`` arrays so they stack cleanly across the worker
axis for the safeguard and across the layer axis for ``lax.scan``).

Implemented temporal-mixing families:
  * GQA/MQA/MHA attention, full or sliding-window, RoPE (standard, partial,
    M-RoPE) or sinusoidal positions — dense, VLM, audio archs;
  * MLA (multi-head latent attention, DeepSeek-V2) with the compressed
    ``c_kv``/``k_rope`` decode cache;
  * RG-LRU recurrent blocks (RecurrentGemma/Griffin);
  * Mamba-2 SSD (state-space duality) with chunked training scan and O(1)
    decode state.

Channel mixing: SwiGLU / GeGLU / GELU MLPs and a dropless MoE whose
layer may hold a share of the experts (sorted dispatch, grouped matmuls).

All matmuls accumulate in float32 (``preferred_element_type``) and softmax
/ norms run in float32 regardless of the compute dtype.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import (flash_attention_train,
                                           train_kernel_fits)

f32 = jnp.float32

# --------------------------------------------------------------------------
# Activation-sharding constraints (enabled by the launch layer only).
#
# Megatron-style TP anchoring: the residual stream is replicated over the
# ``model`` mesh axis; head / ffn / expert dims inside a layer are sharded
# over it.  ``vmap(..., spmd_axis_name=<data axes>)`` in the trainer then
# extends every constraint with the worker axis, which is what keeps the
# per-worker backward pass sharded (XLA's propagation alone drops it inside
# the layer scan and replicates multi-GiB buffers).  Batch/seq dims are
# left UNCONSTRAINED so serving paths can shard them over data.
# --------------------------------------------------------------------------

from jax.sharding import PartitionSpec as _P

_ACT = {"on": False, "model_n": 1, "anchor_residual": True}
_U = _P.UNCONSTRAINED


def enable_activation_sharding(on: bool = True, model_n: int = 1,
                               anchor_residual: bool = True):
    """``anchor_residual``: pin the residual stream (and per-layer block
    outputs) to model-axis replication (Megatron TP convention) — required
    for the vmapped per-worker train path, where propagation otherwise
    drops the worker sharding.  Serving paths (no worker vmap) run better
    *without* the anchor: XLA then keeps the layer carry and all per-token
    ops sequence-sharded and only gathers K/V for attention (a de-facto
    sequence-parallel schedule; see EXPERIMENTS.md §Perf, deepseek-coder
    prefill hillclimb)."""
    _ACT["on"] = on
    _ACT["model_n"] = model_n
    _ACT["anchor_residual"] = anchor_residual


def _mdl(dim_size: int):
    """'model' if the dim can shard over the model axis, else unconstrained."""
    n = _ACT["model_n"]
    return "model" if dim_size % n == 0 and dim_size >= n else _U


def constrain(x, *spec):
    """spec entries: 'model' | None (replicated) | _U (free); per dim."""
    if not _ACT["on"]:
        return x
    if not _ACT["anchor_residual"] and len(spec) == 3 and all(
            s is None or s is _U for s in spec):
        # the (B, L, d) residual / block-output anchors specifically;
        # 4-dim pins (e.g. head_dim = None) stay active in serving mode
        return x
    return jax.lax.with_sharding_constraint(x, _P(*spec))


def _einsum(subscripts, *args, dtype=None):
    """einsum with f32 accumulation, cast back to the first arg's dtype."""
    out_dtype = dtype or args[0].dtype
    return jnp.einsum(subscripts, *args,
                      preferred_element_type=f32).astype(out_dtype)


# ==========================================================================
# Norms
# ==========================================================================

def rms_norm(x, scale, eps: float = 1e-6):
    xf = x.astype(f32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(f32))).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(f32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(f32) + bias.astype(f32)).astype(x.dtype)


def apply_norm(params: Dict, x, kind: str):
    if kind == "rmsnorm":
        return rms_norm(x, params["scale"])
    return layer_norm(x, params["scale"], params["bias"])


def gated_rms_norm(y, z, scale, eps: float = 1e-6):
    """Mamba-2 output norm: RMSNorm(y * silu(z))."""
    yf = y.astype(f32) * jax.nn.silu(z.astype(f32))
    var = jnp.mean(yf * yf, axis=-1, keepdims=True)
    out = yf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(f32))
    return out.astype(y.dtype)


# ==========================================================================
# Positions: RoPE (standard / partial / M-RoPE) and sinusoidal
# ==========================================================================

def rope_cos_sin(positions, dim: int, theta: float):
    """positions (...,) -> cos, sin of shape (..., dim // 2), float32."""
    half = dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=f32) / half)
    ang = positions.astype(f32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def mrope_cos_sin(positions, dim: int, theta: float, sections):
    """M-RoPE (Qwen2-VL): ``positions`` is (3, ...) — temporal, height,
    width ids.  Frequency bands are split into ``sections`` (half-dims
    summing to dim//2); band ``s`` rotates by the s-th position stream."""
    half = dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = theta ** (-jnp.arange(0, half, dtype=f32) / half)
    # (3, ..., half)
    ang = positions.astype(f32)[..., None] * freqs
    chunks, off = [], 0
    for s_idx, s in enumerate(sections):
        chunks.append(ang[s_idx, ..., off:off + s])
        off += s
    ang = jnp.concatenate(chunks, axis=-1)     # (..., half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin, fraction: float = 1.0):
    """x: (B, L, H, D); cos/sin: (B, L, half_rot) or (L, half_rot).

    Rotates the first ``fraction * D`` channels (pairwise split halves, the
    llama/neox convention); the rest pass through (StableLM partial rotary).
    """
    d = x.shape[-1]
    rot = int(d * fraction)
    rot -= rot % 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    while cos.ndim < x1.ndim:                  # broadcast over head axis
        cos, sin = cos[..., None, :], sin[..., None, :]
    x1f, x2f = x1.astype(f32), x2.astype(f32)
    r1 = x1f * cos - x2f * sin
    r2 = x2f * cos + x1f * sin
    out = jnp.concatenate([r1.astype(x.dtype), r2.astype(x.dtype)], axis=-1)
    return jnp.concatenate([out, x_pass], axis=-1)


def sinusoidal_embedding(positions, dim: int):
    """Classic transformer sinusoid table for (B?, L) positions."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=f32) / half)
    ang = positions.astype(f32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ==========================================================================
# Attention core
# ==========================================================================

def _gqa_scores(q, k):
    """q (B,Lq,H,D), k (B,Lk,K,D) -> scores (B,K,H/K,Lq,Lk), f32."""
    B, Lq, H, D = q.shape
    K = k.shape[2]
    qg = q.reshape(B, Lq, K, H // K, D)
    return jnp.einsum("blkgd,bskd->bkgls", qg, k,
                      preferred_element_type=f32)


def attention(q, k, v, *, scale: float, mask):
    """Masked softmax attention with GQA head grouping.

    q: (B, Lq, H, D);  k, v: (B, Lk, K, Dk/Dv);  mask: broadcastable to
    (B, 1, 1, Lq, Lk) (True = attend).  Returns (B, Lq, H, Dv).
    """
    B, Lq, H, _ = q.shape
    K = k.shape[2]
    scores = _gqa_scores(q, k) * scale
    neg = jnp.asarray(-1e30, f32)
    scores = jnp.where(mask, scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgls,bskd->blkgd", probs.astype(v.dtype), v,
                     preferred_element_type=f32).astype(v.dtype)
    return out.reshape(B, Lq, H * v.shape[-1])


def _pick_block(L: int, target: int = 1024) -> int:
    """Largest divisor of L that is <= target (prefers multiples of 128)."""
    best = 1
    for b in range(1, min(L, target) + 1):
        if L % b == 0:
            best = b
    return best


def flash_attention_jnp(q, k, v, *, scale: float, window: int = 0,
                        block_q: int = 1024, block_k: int = 1024):
    """Memory-sane causal attention: O(L * block) live scores instead of
    O(L^2).  Pure-JAX mirror of the Pallas flash kernel (DESIGN.md §5) —
    ``lax.map`` over query blocks (each checkpointed, so the backward pass
    recomputes scores instead of storing them) with an online-softmax scan
    over key blocks.

    q: (B, Lq, H, Dk);  k: (B, S, H, Dk);  v: (B, S, H, Dv) — MHA layout:
    GQA callers expand K/V to H heads first.  Splitting H into (kv_head,
    group) here would make the head axis un-shardable on the ``model``
    mesh axis and force XLA into full rematerialization; the expanded
    copy is cheap (O(S*H*D)) and keeps the head dim intact.
    Keys are contiguous from position 0 and Lq == S (train/prefill path).
    Returns (B, Lq, H, Dv).
    """
    B, Lq, H, Dk = q.shape
    S = k.shape[1]
    Dv = v.shape[-1]
    bq = _pick_block(Lq, block_q)
    bk = _pick_block(S, block_k)
    nq, nk = Lq // bq, S // bk

    qb = jnp.moveaxis(q.reshape(B, nq, bq, H, Dk), 1, 0)   # (nq, B, bq, H, Dk)
    kb = jnp.moveaxis(k.reshape(B, nk, bk, H, Dk), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, bk, H, Dv), 1, 0)

    kpos = jnp.arange(nk * bk).reshape(nk, bk)

    @jax.checkpoint
    def one_q_block(args):
        qi, iq = args                                      # (B, bq, H, Dk)
        qi = constrain(qi, _U, _U, _mdl(H), None)
        qpos = iq * bq + jnp.arange(bq)

        def kv_step(carry, xs):
            mx, l, acc = carry
            kblk, vblk, kp = xs
            s = jnp.einsum("bqhd,bshd->bhqs", qi, kblk,
                           preferred_element_type=f32) * scale
            s = constrain(s, _U, _mdl(H), _U, _U)
            mask = kp[None, :] <= qpos[:, None]
            if window > 0:
                mask &= kp[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, None], s, -1e30)
            m_new = jnp.maximum(mx, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(mx - m_new)
            l = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bhqs,bshd->bhqd", p.astype(v.dtype), vblk,
                            preferred_element_type=f32)
            acc = acc * alpha[..., None] + pv
            return (m_new, l, acc), None

        init = (jnp.full((B, H, bq), -1e30, f32),
                jnp.zeros((B, H, bq), f32),
                jnp.zeros((B, H, bq, Dv), f32))
        (mx, l, acc), _ = jax.lax.scan(kv_step, init, (kb, vb, kpos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]       # (B, H, bq, Dv)
        return jnp.moveaxis(out, 2, 1).astype(q.dtype)     # (B, bq, H, Dv)

    outs = jax.lax.map(one_q_block, (qb, jnp.arange(nq)))  # (nq, B, bq, H, Dv)
    return jnp.moveaxis(outs, 0, 1).reshape(B, Lq, H, Dv)


# sequence length above which the train/prefill path switches from dense
# masked attention to the blocked flash path
FLASH_THRESHOLD = 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _flash_kernel_fits(q, v, *, window: int) -> bool:
    """Whether the blocked flash path can run as the Pallas kernel
    (``flash_attention_train``) rather than ``flash_attention_jnp``: on the
    TPU, causal with no window, equal q/k and v head sizes, the sequence a
    multiple of the kernel's blocks, heads not split over a ``model`` axis
    and no context mesh of several devices (a Mosaic kernel is not
    partitioned by the compiler)."""
    return (_on_tpu() and window == 0 and q.shape[-1] == v.shape[-1]
            and train_kernel_fits(q.shape[1])
            and (not _ACT["on"] or _ACT["model_n"] == 1)
            and jax.sharding.get_abstract_mesh().size <= 1)


def causal_mask(Lq: int, Lk: int, *, q_offset=0, window: int = 0):
    """(Lq, Lk) boolean mask; query i sits at absolute position
    ``q_offset + i``, key j at absolute position j.  ``window`` > 0 further
    restricts to the last ``window`` positions (sliding window)."""
    qpos = jnp.arange(Lq)[:, None] + q_offset
    kpos = jnp.arange(Lk)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m


# ==========================================================================
# GQA attention block (dense / vlm / audio / hybrid-attn layers)
# ==========================================================================

def _pos_cos_sin(cfg, positions):
    if cfg.pos == "rope":
        rot = int(cfg.head_dim * cfg.rope_fraction)
        rot -= rot % 2
        return rope_cos_sin(positions, rot, cfg.rope_theta)
    if cfg.pos == "mrope":
        return mrope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                             cfg.mrope_sections)
    return None, None


def ring_from_full(full, S: int):
    """Pack the last ``min(L, S)`` timesteps of a full-sequence tensor
    (B, L, ...) into a ring buffer of size S:  absolute position p lives at
    slot ``p % S``.  Static shapes — indices resolved at trace time."""
    B, Lf = full.shape[0], full.shape[1]
    keep = min(Lf, S)
    p0 = Lf - keep
    ring = jnp.zeros((B, S) + full.shape[2:], full.dtype)
    slots = (p0 + jnp.arange(keep)) % S
    return ring.at[:, slots].set(full[:, p0:])


def attn_block_apply(params, cfg, x, *, positions, cache=None,
                     cache_pos=None, max_seq: int = 0):
    """One attention layer (projections + rope + cache + attention + out).

    Train/prefill: ``cache is None`` -> full causal (+window) attention
    over ``x`` (B, L, d); with ``max_seq > 0`` (prefill) the returned cache
    is a ring buffer of that size, otherwise the raw (L-long) k/v.
    Decode: ``cache`` = {"k","v"} ring/full buffers, ``cache_pos`` scalar
    absolute position of the incoming token; L == 1.
    """
    B, L, _ = x.shape
    H, K, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    q = _einsum("bld,dhq->blhq", x,
                params["wq"].reshape(cfg.d_model, H, Dh))
    k = _einsum("bld,dkq->blkq", x,
                params["wk"].reshape(cfg.d_model, K, Dh))
    v = _einsum("bld,dkq->blkq", x,
                params["wv"].reshape(cfg.d_model, K, Dh))
    # head_dim pinned to None (replicated): when H doesn't divide the
    # model axis XLA otherwise factorizes the fused H*Dh dim as
    # (heads x head_dim) shards, making attention contract a sharded
    # D => one psum per flash block (55 TB/device on deepseek-coder
    # prefill; EXPERIMENTS.md §Perf)
    q = constrain(q, _U, _U, _mdl(H), None)
    k = constrain(k, _U, _U, _mdl(K), None)
    v = constrain(v, _U, _U, _mdl(K), None)

    cos, sin = _pos_cos_sin(cfg, positions)
    if cos is not None:
        q = apply_rope(q, cos, sin, cfg.rope_fraction)
        k = apply_rope(k, cos, sin, cfg.rope_fraction)

    scale = cfg.attn_scale or 1.0 / math.sqrt(Dh)
    window = cfg.window if cfg.attn == "sliding" else 0

    if cache is None:
        if L >= FLASH_THRESHOLD and _flash_kernel_fits(q, v, window=window):
            with jax.named_scope("attn_kernel"):
                out = flash_attention_train(q, k, v, scale=scale)
        elif L >= FLASH_THRESHOLD:
            with jax.named_scope("attn_jnp"):
                kx = jnp.repeat(k, H // K, axis=2)  # expand GQA -> MHA so
                vx = jnp.repeat(v, H // K, axis=2)  # the head dim shards
                kx = constrain(kx, _U, _U, _mdl(H), None)
                vx = constrain(vx, _U, _U, _mdl(H), None)
                out = flash_attention_jnp(q, kx, vx, scale=scale,
                                          window=window)
                out = out.reshape(B, L, H * Dh)
        else:
            mask = causal_mask(L, L, window=window)[None, None, None]
            out = attention(q, k, v, scale=scale, mask=mask)
        if max_seq > 0:
            S = min(max_seq, window) if window > 0 else max_seq
            new_cache = {"k": ring_from_full(k, S),
                         "v": ring_from_full(v, S)}
        else:
            new_cache = ()
    else:
        S = cache["k"].shape[1]                # ring size (or max seq)
        slot = cache_pos % S
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
        # absolute position held by ring slot j after the write:
        #   abs_j = cache_pos - ((cache_pos - j) mod S)   in (cache_pos-S, cache_pos]
        j = jnp.arange(S)
        abs_j = cache_pos - ((cache_pos - j) % S)
        valid = abs_j >= 0
        if window > 0:
            valid &= abs_j > cache_pos - window
        mask = valid[None, None, None, None, :]
        out = attention(q, ck, cv, scale=scale, mask=mask)
        new_cache = {"k": ck, "v": cv}

    out = _einsum("blf,fd->bld", out, params["wo"])
    out = constrain(out, _U, _U, None)
    return out, new_cache


def attn_block_init(key, cfg, init_scale=0.02):
    H, K, Dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    k1, k2, k3, k4 = jax.random.split(key, 4)
    pd = cfg.param_dtype
    mk = lambda k, shape: (init_scale * jax.random.normal(k, shape)).astype(pd)
    return {
        "wq": mk(k1, (d, H * Dh)),
        "wk": mk(k2, (d, K * Dh)),
        "wv": mk(k3, (d, K * Dh)),
        "wo": mk(k4, (H * Dh, d)),
    }


def attn_cache_init(cfg, batch: int, max_seq: int, dtype):
    S = max_seq if cfg.attn != "sliding" else min(max_seq, cfg.window)
    K, Dh = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, S, K, Dh), dtype),
        "v": jnp.zeros((batch, S, K, Dh), dtype),
    }


# ==========================================================================
# MLA block (DeepSeek-V2)
# ==========================================================================

def mla_block_apply(params, cfg, x, *, positions, cache=None,
                    cache_pos=None, max_seq: int = 0):
    """Multi-head latent attention (DeepSeek-V2).

    Caches the compressed ``c_kv`` (kv_lora_rank) and the shared roped key
    ``k_rope`` — the order-of-magnitude-smaller decode cache that defines
    MLA.

    TPU adaptation (DESIGN.md §4): the *train/prefill* path expands
    per-head keys/values from the latent and runs the blocked flash path
    (cheapest FLOPs; expansion is O(L), fine when scores are blocked);
    the *decode* path uses **weight absorption** — queries are pushed
    through W_uk ("q_lat = q_nope W_uk") and attention runs directly
    against the latent cache, so no (B, S, H, dn) expansion of a 32k+
    cache ever materializes.
    """
    B, L, d = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank

    # --- queries (optionally low-rank) -----------------------------------
    if cfg.q_lora_rank > 0:
        cq = _einsum("bld,dq->blq", x, params["w_dq"])
        cq = rms_norm(cq, params["q_norm_scale"])
        q = _einsum("blq,qhf->blhf", cq,
                    params["w_uq"].reshape(cfg.q_lora_rank, H, dn + dr))
    else:
        q = _einsum("bld,dhf->blhf", x,
                    params["w_uq"].reshape(d, H, dn + dr))
    q = constrain(q, _U, _U, _mdl(H), None)
    q_nope, q_rope = q[..., :dn], q[..., dn:]

    # --- compressed kv ----------------------------------------------------
    c_kv = _einsum("bld,dq->blq", x, params["w_dkv"])
    c_kv = rms_norm(c_kv, params["kv_norm_scale"])
    k_rope = _einsum("bld,dr->blr", x, params["w_kr"])    # shared per token

    cos, sin = rope_cos_sin(positions, dr, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]

    scale = 1.0 / math.sqrt(dn + dr)
    w_uk = params["w_uk"].reshape(r, H, dn)
    w_uv = params["w_uv"].reshape(r, H, dv)

    if cache is None:
        # ---- train / prefill: expanded per-head K/V + flash --------------
        k_nope = constrain(_einsum("bsq,qhf->bshf", c_kv, w_uk),
                           _U, _U, _mdl(H), None)
        value = constrain(_einsum("bsq,qhf->bshf", c_kv, w_uv),
                          _U, _U, _mdl(H), None)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, L, H, dr)).astype(k_nope.dtype)],
            axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope.astype(q_nope.dtype)],
                                 axis=-1)
        if L >= FLASH_THRESHOLD:
            out = flash_attention_jnp(q_full, k_full, value, scale=scale)
        else:
            mask = causal_mask(L, L)[None, None, None]
            out = attention(q_full, k_full, value, scale=scale, mask=mask)
            out = out.reshape(B, L, H, dv)
        if max_seq > 0:
            new_cache = {"c_kv": ring_from_full(c_kv, max_seq),
                         "k_rope": ring_from_full(k_rope, max_seq)}
        else:
            new_cache = ()
    else:
        # ---- decode: absorbed attention against the latent cache ---------
        S = cache["c_kv"].shape[1]
        slot = cache_pos % S
        c_kv = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv, slot, axis=1)
        k_rope = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope, slot, axis=1)
        new_cache = {"c_kv": c_kv, "k_rope": k_rope}
        j = jnp.arange(S)
        abs_j = cache_pos - ((cache_pos - j) % S)
        mask = (abs_j >= 0)[None, None, None, :]           # (1,1,1,S)

        q_lat = _einsum("blhn,rhn->blhr", q_nope, w_uk)    # absorb W_uk
        scores = (
            jnp.einsum("blhr,bsr->bhls", q_lat, c_kv,
                       preferred_element_type=f32)
            + jnp.einsum("blhr,bsr->bhls", q_rope, k_rope,
                         preferred_element_type=f32)
        ) * scale
        scores = constrain(scores, _U, _mdl(H), _U, _U)
        scores = jnp.where(mask, scores, jnp.asarray(-1e30, f32))
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        o_lat = _einsum("bhls,bsr->blhr", probs, c_kv)
        out = _einsum("blhr,rhv->blhv", o_lat, w_uv)       # absorb W_uv

    out = out.reshape(B, L, H * dv)
    out = _einsum("blf,fd->bld", out, params["wo"])
    out = constrain(out, _U, _U, None)
    return out, new_cache


def mla_block_init(key, cfg, init_scale=0.02):
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 7)
    pd = cfg.param_dtype
    mk = lambda k, shape: (init_scale * jax.random.normal(k, shape)).astype(pd)
    p = {
        "w_dkv": mk(ks[0], (d, cfg.kv_lora_rank)),
        "kv_norm_scale": jnp.zeros((cfg.kv_lora_rank,), pd),
        "w_uk": mk(ks[1], (cfg.kv_lora_rank, H * dn)),
        "w_uv": mk(ks[2], (cfg.kv_lora_rank, H * dv)),
        "w_kr": mk(ks[3], (d, dr)),
        "wo": mk(ks[4], (H * dv, d)),
    }
    if cfg.q_lora_rank > 0:
        p["w_dq"] = mk(ks[5], (d, cfg.q_lora_rank))
        p["q_norm_scale"] = jnp.zeros((cfg.q_lora_rank,), pd)
        p["w_uq"] = mk(ks[6], (cfg.q_lora_rank, H * (dn + dr)))
    else:
        p["w_uq"] = mk(ks[6], (d, H * (dn + dr)))
    return p


def mla_cache_init(cfg, batch: int, max_seq: int, dtype):
    return {
        "c_kv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_seq, cfg.qk_rope_dim), dtype),
    }


# ==========================================================================
# MLPs
# ==========================================================================

def mlp_apply(params, kind: str, x):
    ff = params["w_up"].shape[-1]
    spec = (_U,) * (x.ndim - 1) + (_mdl(ff),)
    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else jax.nn.gelu
        gate = act(constrain(_einsum("bld,df->blf", x, params["w_gate"],
                                     dtype=f32), *spec))
        up = constrain(_einsum("bld,df->blf", x, params["w_up"], dtype=f32),
                       *spec)
        h = (gate * up).astype(x.dtype)
    else:  # plain gelu
        h = jax.nn.gelu(constrain(
            _einsum("bld,df->blf", x, params["w_up"], dtype=f32),
            *spec)).astype(x.dtype)
    out = _einsum("blf,fd->bld", h, params["w_down"])
    return constrain(out, _U, _U, None)


def mlp_init(key, kind: str, d: int, d_ff: int, param_dtype,
             init_scale=0.02):
    ks = jax.random.split(key, 3)
    mk = lambda k, shape: (init_scale * jax.random.normal(k, shape)
                           ).astype(param_dtype)
    if kind in ("swiglu", "geglu"):
        return {"w_gate": mk(ks[0], (d, d_ff)),
                "w_up": mk(ks[1], (d, d_ff)),
                "w_down": mk(ks[2], (d_ff, d))}
    return {"w_up": mk(ks[0], (d, d_ff)), "w_down": mk(ks[1], (d_ff, d))}


# ==========================================================================
# MoE: dropless routed experts, of which a layer may hold a share
# ==========================================================================

def _ragged_dot(lhs, rhs, group_sizes):
    """``(N, k) x (G, k, n) -> (N, n)``: row block ``g`` of ``lhs`` (the
    ``group_sizes[g]`` rows after the blocks before it) times ``rhs[g]``;
    rows past the last block are not computed and read 0."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=f32)


_RAGGED_CONTRACT = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _ragged_dot_t(lhs, rhs, group_sizes):
    """``(N, k)^T x (N, n) -> (G, k, n)``: per row block ``g``, the block
    of ``lhs`` transposed times the block of ``rhs``."""
    return jax.lax.ragged_dot_general(lhs, rhs, group_sizes,
                                      _RAGGED_CONTRACT,
                                      preferred_element_type=f32)


def _lanes_in_turn(f):
    """``f`` under ``vmap`` runs each lane in turn: the TPU's ragged dot
    takes no batch dimension.  Where the context mesh (which
    ``launch.train.build_trainer`` sets around its step) splits the lanes
    over its worker axes, as the trainer's worker ``vmap`` is split, each
    shard runs its own lanes: a loop over the whole axis would have every
    shard gather and compute every worker's."""
    f = jax.custom_batching.custom_vmap(f)

    @f.def_vmap
    def rule(axis_size, in_batched, *args):
        def lanes(*args):
            def one(mapped):
                it = iter(mapped)
                return f(*[next(it) if b else a
                           for a, b in zip(args, in_batched)])
            return jax.lax.map(one, [a for a, b in zip(args, in_batched)
                                     if b])

        mesh = jax.sharding.get_abstract_mesh()
        axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        n = math.prod(mesh.shape[a] for a in axes)
        if n > 1 and axis_size % n == 0:
            lanes = jax.shard_map(
                lanes, mesh=mesh, out_specs=_P(axes),
                in_specs=tuple(_P(axes) if b else _P() for b in in_batched))
        return lanes(*args), True

    return f


_gmm = _lanes_in_turn(_ragged_dot)
_gmm_t = _lanes_in_turn(_ragged_dot_t)


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """The experts' grouped matmul: rows sorted by expert, ``rhs`` the
    stacked expert weights, f32 out.  Its backward is two more grouped
    matmuls (the rows' cotangent, the weights' per expert)."""
    return _gmm(lhs, rhs, group_sizes)


def _grouped_matmul_fwd(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    d_lhs = _gmm(g, jnp.swapaxes(rhs, 1, 2), group_sizes)
    d_rhs = _gmm_t(lhs, g, group_sizes)
    return d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype), None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def _take_slots(rows, slot):
    """``rows[slot]`` for ``slot < len(rows)``, else 0: ``(T, K, d)``."""
    n = rows.shape[0]
    got = rows[jnp.minimum(slot, n - 1)]
    return jnp.where((slot < n)[..., None], got, jnp.zeros((), rows.dtype))


# Dispatch and combine move rows by a permutation (token assignment <->
# expert-sorted row), so each direction of each is a gather: the backward
# passes gather through the inverse map instead of scattering.

@jax.custom_vjp
def _dispatch(x, assign, slot):
    """The rows of the expert buffer: row ``r`` is the token of assignment
    ``assign[r]`` (``x``: (T, d), ``K`` assignments per token).  ``slot``
    (T, K) is the inverse map: the row of each assignment, or the row
    count where it has none."""
    return x[assign // slot.shape[1]]


def _dispatch_fwd(x, assign, slot):
    return _dispatch(x, assign, slot), (assign, slot)


def _dispatch_bwd(res, g):
    _, slot = res
    return _take_slots(g, slot).astype(f32).sum(1).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, weights, assign, slot):
    """Each token's weighted sum of its assignments' rows, f32 (T, d)."""
    return (_take_slots(rows, slot).astype(f32) * weights[..., None]).sum(1)


def _combine_fwd(rows, weights, assign, slot):
    return _combine(rows, weights, assign, slot), (rows, weights, assign,
                                                   slot)


def _combine_bwd(res, g):
    rows, weights, assign, slot = res
    K = slot.shape[1]
    d_weights = (_take_slots(rows, slot).astype(f32) * g[:, None]).sum(-1)
    # a row whose assignment points back at it is routed; the rest of the
    # buffer holds other experts' assignments and gets nothing
    owned = slot.reshape(-1)[assign] == jnp.arange(rows.shape[0])
    w_row = jnp.where(owned, weights.reshape(-1)[assign], 0.0)
    d_rows = (g[assign // K] * w_row[:, None]).astype(rows.dtype)
    return d_rows, d_weights, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_apply(params, cfg, x):
    """Routed experts (top-k of the router's logits, softmax over the k
    chosen) + optional shared experts, with no capacity: every assignment
    to a held expert is computed.

    The router scores all ``cfg.n_experts``; the layer holds the weights
    of ``cfg.held_experts`` and computes exactly the assignments routed to
    them: assignments sorted by held expert (the rest after them), the
    tokens gathered into that order, three grouped matmuls over the rows
    and the weighted sum back per token.  What the experts it does not
    hold would add is left out.  The buffer has ``T * min(K, G)`` rows,
    enough for any routing of ``T`` tokens to ``G`` held experts.

    Returns ``(y, aux)``: ``aux["moe_aux"]`` is the switch-style
    load-balance loss over all ``n_experts``; ``expert_rows_max`` and
    ``expert_rows_sum`` are the largest and the total number of rows the
    held experts computed.
    """
    B, L, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    held = cfg.held_experts
    G = len(held)
    T = B * L
    N = T * min(K, G)
    xt = x.reshape(T, d)

    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            logits = _einsum("td,de->te", xt, params["router"], dtype=f32)
            top_l, top_e = jax.lax.top_k(logits, K)            # (T, K)
            weights = jax.nn.softmax(top_l, axis=-1)
            # load-balance aux (Switch): E * sum_e f_e * P_e
            me = jax.nn.softmax(logits, axis=-1).mean(axis=0)  # (E,)
            ce = jnp.zeros((E,), f32).at[top_e.reshape(-1)].add(1.0) / (T * K)
            aux = E * jnp.sum(me * ce)

        with jax.named_scope("dispatch"):
            local = top_e.reshape(-1) - held.start             # (T*K,)
            key = jnp.where((local >= 0) & (local < G), local, G)
            order = jnp.argsort(key, stable=True)
            group_sizes = (key[:, None] == jnp.arange(G)).sum(
                0, dtype=jnp.int32)
            rank = jnp.zeros((T * K,), jnp.int32).at[order].set(
                jnp.arange(T * K, dtype=jnp.int32), unique_indices=True)
            slot = jnp.where(key < G, rank, N).reshape(T, K)
            assign = order[:N]
            rows = _dispatch(xt, assign, slot)                 # (N, d)

        with jax.named_scope("experts"):
            gate = jax.nn.silu(grouped_matmul(rows, params["w_gate"],
                                              group_sizes))
            up = grouped_matmul(rows, params["w_up"], group_sizes)
            out = grouped_matmul((gate * up).astype(x.dtype),
                                 params["w_down"], group_sizes)

        with jax.named_scope("combine"):
            y = _combine(out.astype(x.dtype), weights, assign, slot)

    if cfg.n_shared_experts > 0:
        y = y + mlp_apply(params["shared"], "swiglu", x).reshape(T, d)

    stats = {"moe_aux": aux,
             "expert_rows_max": group_sizes.max().astype(f32),
             "expert_rows_sum": group_sizes.sum().astype(f32)}
    return y.reshape(B, L, d).astype(x.dtype), stats


def moe_init(key, cfg, init_scale=0.02):
    """The router over all ``n_experts`` (f32) and the held experts'
    stacked SwiGLU weights, ``(G, d, f)`` and ``(G, f, d)``."""
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.d_expert
    G = len(cfg.held_experts)
    ks = jax.random.split(key, 5)
    pd = cfg.param_dtype
    mk = lambda k, shape: (init_scale * jax.random.normal(k, shape)).astype(pd)
    p = {
        "router": mk(ks[0], (d, E)).astype(f32),   # router in f32
        "w_gate": mk(ks[1], (G, d, ff)),
        "w_up": mk(ks[2], (G, d, ff)),
        "w_down": mk(ks[3], (G, ff, d)),
    }
    if cfg.n_shared_experts > 0:
        p["shared"] = mlp_init(ks[4], "swiglu", d,
                               cfg.n_shared_experts * ff, pd, init_scale)
    return p


# ==========================================================================
# RG-LRU recurrent block (RecurrentGemma / Griffin)
# ==========================================================================

_RGLRU_C = 8.0


def _rglru_scan(a, b, h0=None):
    """h_t = a_t * h_{t-1} + b_t  along axis 1, via associative scan.
    a, b: (B, L, D) f32.  Returns (h (B, L, D), h_last (B, D))."""
    if h0 is not None:
        # fold the initial state into the first step
        b = b.at[:, 0].add(a[:, 0] * h0)
    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2
    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h, h[:, -1]


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv, width W.  x: (B, L, C), w: (W, C).
    ``state``: (B, W-1, C) trailing context for decode; returns
    (y, new_state)."""
    W = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)          # (B, L+W-1, C)
    yf = jnp.zeros(x.shape, f32)
    for i in range(W):
        yf = yf + xp[:, i:i + x.shape[1]].astype(f32) * w[i].astype(f32)
    y = (yf + b.astype(f32)).astype(x.dtype)
    new_state = xp[:, -(W - 1):] if W > 1 else pad
    return y, new_state


def rglru_block_apply(params, cfg, x, *, cache=None):
    """Griffin recurrent block: conv -> RG-LRU, gated by a GeLU branch.

    cache (decode): {"conv": (B, W-1, lru), "h": (B, lru)}.
    """
    B, L, d = x.shape
    lru = cfg.lru_width

    branch = constrain(_einsum("bld,df->blf", x, params["w_x"]),
                       _U, _U, _mdl(lru))
    gate_branch = jax.nn.gelu(constrain(
        _einsum("bld,df->blf", x, params["w_y"], dtype=f32),
        _U, _U, _mdl(lru)))

    conv_state = cache["conv"] if cache is not None else None
    u, new_conv = _causal_conv(branch, params["conv_w"], params["conv_b"],
                               conv_state)

    uf = u.astype(f32)
    r = jax.nn.sigmoid(_einsum("blf,fg->blg", u, params["w_r"], dtype=f32)
                       + params["b_r"].astype(f32))
    i = jax.nn.sigmoid(_einsum("blf,fg->blg", u, params["w_i"], dtype=f32)
                       + params["b_i"].astype(f32))
    log_a_base = jax.nn.log_sigmoid(params["a_param"].astype(f32))
    log_a = _RGLRU_C * r * log_a_base                 # (B, L, lru), <= 0
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    b = beta * (i * uf)

    h0 = cache["h"].astype(f32) if cache is not None else None
    h, h_last = _rglru_scan(a, b, h0)

    out = (h * gate_branch).astype(x.dtype)
    out = _einsum("blf,fd->bld", out, params["w_o"])
    out = constrain(out, _U, _U, None)
    new_cache = {"conv": new_conv, "h": h_last.astype(x.dtype)}
    return out, new_cache


def rglru_block_init(key, cfg, init_scale=0.02):
    d, lru = cfg.d_model, cfg.lru_width
    ks = jax.random.split(key, 7)
    pd = cfg.param_dtype
    mk = lambda k, shape: (init_scale * jax.random.normal(k, shape)).astype(pd)
    # a_param initialized so that a^c is in [0.9, 0.999] (Griffin)
    u = jax.random.uniform(ks[5], (lru,), f32, 0.9, 0.999)
    a_param = jnp.log(u ** (1.0 / _RGLRU_C) / (1 - u ** (1.0 / _RGLRU_C)))
    return {
        "w_x": mk(ks[0], (d, lru)),
        "w_y": mk(ks[1], (d, lru)),
        "conv_w": mk(ks[2], (cfg.d_conv, lru)),
        "conv_b": jnp.zeros((lru,), pd),
        "w_r": mk(ks[3], (lru, lru)),
        "b_r": jnp.zeros((lru,), pd),
        "w_i": mk(ks[4], (lru, lru)),
        "b_i": jnp.zeros((lru,), pd),
        "a_param": a_param.astype(f32),
        "w_o": mk(ks[6], (lru, d)),
    }


def rglru_cache_init(cfg, batch: int, dtype):
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.lru_width), dtype),
        "h": jnp.zeros((batch, cfg.lru_width), dtype),
    }


# ==========================================================================
# Mamba-2 SSD block
# ==========================================================================

def _segsum(x):
    """x (..., K) -> (..., K, K) lower-triangular inclusive-of-diagonal
    cumulative sums: out[i, j] = sum_{j < t <= i} x[t]  (0 on diagonal,
    -inf above)."""
    K = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((K, K), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_scan(x, dt, A, Bm, Cm, chunk: int, init_state=None):
    """Chunked SSD (Dao & Gu 2024, 'minimal' algorithm).

    x:  (b, l, h, p)   inputs per head
    dt: (b, l, h)      discretization steps (post-softplus)
    A:  (h,)           negative decay rates
    Bm, Cm: (b, l, g, n)   input/output projections (g groups)
    Returns y (b, l, h, p), final_state (b, h, p, n).
    """
    b, l, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    assert l % chunk == 0, (l, chunk)
    c = l // chunk
    rep = h // g
    Bh = jnp.repeat(Bm, rep, axis=2)            # (b, l, h, n)
    Ch = jnp.repeat(Cm, rep, axis=2)

    # chunked views
    xc = x.reshape(b, c, chunk, h, p).astype(f32)
    dtc = dt.reshape(b, c, chunk, h).astype(f32)
    Bc = Bh.reshape(b, c, chunk, h, n).astype(f32)
    Cc = Ch.reshape(b, c, chunk, h, n).astype(f32)

    dtA = dtc * A.astype(f32)                   # (b, c, k, h)
    dtA_h = jnp.moveaxis(dtA, -1, -2)           # (b, c, h, k)
    L = jnp.exp(_segsum(dtA_h))                 # (b, c, h, k, k)

    xdt = xc * dtc[..., None]                   # (b, c, k, h, p)

    # intra-chunk (diagonal) term
    y_diag = jnp.einsum("bckhn,bclhn,bchkl,bclhp->bckhp", Cc, Bc, L, xdt)

    # per-chunk input states
    cum = jnp.cumsum(dtA_h, axis=-1)            # (b, c, h, k)
    total = cum[..., -1:]                       # (b, c, h, 1)
    decay_to_end = jnp.exp(total - cum)         # (b, c, h, k)
    states = jnp.einsum("bclhn,bchl,bclhp->bchpn", Bc, decay_to_end, xdt)

    # inter-chunk recurrence over c
    chunk_decay = jnp.exp(total[..., 0])        # (b, c, h)
    s0 = (jnp.zeros((b, h, p, n), f32) if init_state is None
          else init_state.astype(f32))

    def step(s, inp):
        dec, st = inp
        s_new = s * dec[..., None, None] + st
        return s_new, s

    xs = (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0))
    final, prev_states = jax.lax.scan(step, s0, xs)
    prev_states = jnp.moveaxis(prev_states, 0, 1)   # (b, c, h, p, n)

    # contribution of the carried-in state
    state_decay = jnp.exp(cum)                  # (b, c, h, k)
    y_off = jnp.einsum("bckhn,bchpn,bchk->bckhp", Cc, prev_states,
                       state_decay)

    y = (y_diag + y_off).reshape(b, l, h, p)
    return y.astype(x.dtype), final


def ssd_decode_step(state, x, dt, A, Bm, Cm):
    """Single-token SSD update.  x: (b, h, p), dt: (b, h), Bm/Cm: (b, g, n).
    state: (b, h, p, n) -> new state, y (b, h, p)."""
    g = Bm.shape[1]
    rep = x.shape[1] // g
    Bh = jnp.repeat(Bm, rep, axis=1).astype(f32)       # (b, h, n)
    Ch = jnp.repeat(Cm, rep, axis=1).astype(f32)
    dtf = dt.astype(f32)
    dA = jnp.exp(dtf * A.astype(f32))                  # (b, h)
    xdt = x.astype(f32) * dtf[..., None]               # (b, h, p)
    new_state = (state.astype(f32) * dA[..., None, None]
                 + xdt[..., None] * Bh[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    return new_state.astype(state.dtype), y.astype(x.dtype)


def mamba2_block_apply(params, cfg, x, *, cache=None):
    """Mamba-2 mixer.  cache (decode): {"conv": (B, W-1, convw),
    "ssm": (B, h, p, n)}."""
    B, L, d = x.shape
    di = cfg.d_inner
    h, p = cfg.n_ssm_heads, cfg.ssm_head_dim
    g, n = cfg.n_groups, cfg.d_state

    zxbcdt = constrain(_einsum("bld,df->blf", x, params["in_proj"]),
                       _U, _U, _U)
    z, xin, Braw, Craw, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + g * n, 2 * di + 2 * g * n], axis=-1)

    conv_in = jnp.concatenate([xin, Braw, Craw], axis=-1)
    conv_state = cache["conv"] if cache is not None else None
    conv_out, new_conv = _causal_conv(conv_in, params["conv_w"],
                                      params["conv_b"], conv_state)
    conv_out = jax.nn.silu(conv_out.astype(f32)).astype(x.dtype)
    xs, Braw, Craw = jnp.split(conv_out, [di, di + g * n], axis=-1)

    xs = xs.reshape(B, L, h, p)
    Bm = Braw.reshape(B, L, g, n)
    Cm = Craw.reshape(B, L, g, n)
    dt = jax.nn.softplus(dt.astype(f32)
                         + params["dt_bias"].astype(f32))  # (B, L, h)
    A = -jnp.exp(params["A_log"].astype(f32))              # (h,)

    if cache is None:
        # pad to a chunk multiple
        pad = (-L) % cfg.ssm_chunk
        if pad:
            zp = lambda t: jnp.pad(t, [(0, 0), (0, pad)]
                                   + [(0, 0)] * (t.ndim - 2))
            xs, dt, Bm, Cm = map(zp, (xs, dt, Bm, Cm))
        with jax.named_scope("ssd"):
            y, final = ssd_scan(xs, dt, A, Bm, Cm, cfg.ssm_chunk)
        y = y[:, :L]
        new_ssm = final.astype(x.dtype)
    else:
        new_ssm, y1 = ssd_decode_step(
            cache["ssm"], xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0])
        y = y1[:, None]

    y = y + xs[:, :y.shape[1]] * params["D"].astype(f32)[None, None, :, None
                                                         ].astype(x.dtype)
    y = y.reshape(B, L, di)
    y = gated_rms_norm(y, z, params["norm_scale"])
    out = _einsum("blf,fd->bld", y, params["out_proj"])
    out = constrain(out, _U, _U, None)
    new_cache = {"conv": new_conv, "ssm": new_ssm}
    return out, new_cache


def mamba2_block_init(key, cfg, init_scale=0.02):
    d, di = cfg.d_model, cfg.d_inner
    h = cfg.n_ssm_heads
    g, n = cfg.n_groups, cfg.d_state
    convw = di + 2 * g * n
    proj_out = 2 * di + 2 * g * n + h
    ks = jax.random.split(key, 4)
    pd = cfg.param_dtype
    mk = lambda k, shape: (init_scale * jax.random.normal(k, shape)).astype(pd)
    dt_init = jnp.log(jnp.expm1(
        jnp.exp(jax.random.uniform(ks[2], (h,), f32,
                                   jnp.log(1e-3), jnp.log(1e-1)))))
    return {
        "in_proj": mk(ks[0], (d, proj_out)),
        "conv_w": mk(ks[1], (cfg.d_conv, convw)),
        "conv_b": jnp.zeros((convw,), pd),
        "A_log": jnp.log(jnp.arange(1, h + 1, dtype=f32)),
        "D": jnp.ones((h,), f32),
        "dt_bias": dt_init,
        "norm_scale": jnp.zeros((di,), pd),
        "out_proj": mk(ks[3], (di, d)),
    }


def mamba2_cache_init(cfg, batch: int, dtype):
    convw = cfg.d_inner + 2 * cfg.n_groups * cfg.d_state
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, convw), dtype),
        "ssm": jnp.zeros((batch, cfg.n_ssm_heads, cfg.ssm_head_dim,
                          cfg.d_state), dtype),
    }
