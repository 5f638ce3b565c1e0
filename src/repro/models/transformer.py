"""Model assembly: init / forward / loss / prefill / decode for every
assigned architecture, driven entirely by ``ModelConfig``.

Layers are stacked along a leading layer axis and executed with
``jax.lax.scan`` (constant compile time in depth — critical for the
88-layer dry runs).  Heterogeneous stacks are split into homogeneous
scan groups:

  * dense / vlm / audio:      one scan over identical attention blocks;
  * moe (granite-moe):        one scan over attention+MoE blocks;
  * moe (deepseek-v2):        layer 0 (dense FFN) unrolled, scan over the
                              remaining MLA+MoE blocks;
  * ssm (mamba2):             one scan over SSD blocks;
  * hybrid (recurrentgemma):  scan over (rec, rec, attn) super-blocks plus
                              unrolled trailing rec layers (26 = 3*8 + 2).

The decode cache mirrors the same grouping so it scans along with the
parameters.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

f32 = jnp.float32


# ==========================================================================
# Parameter initialization
# ==========================================================================

def _norm_init(cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.zeros((d,), cfg.param_dtype)}
    return {"scale": jnp.ones((d,), cfg.param_dtype),
            "bias": jnp.zeros((d,), cfg.param_dtype)}


def _layer_kind(cfg: ModelConfig, idx: int) -> str:
    if cfg.ssm:
        return "ssm"
    if cfg.hybrid:
        return "attn" if idx % 3 == 2 else "rec"
    if cfg.n_experts > 0:
        if idx < cfg.first_k_dense:
            return "mla_dense" if cfg.use_mla else "attn_dense_wide"
        return "mla_moe" if cfg.use_mla else "attn_moe"
    return "attn"


def _layer_init(key, cfg: ModelConfig, kind: str):
    ks = jax.random.split(key, 4)
    if kind == "ssm":
        return {"ln": _norm_init(cfg),
                "mixer": L.mamba2_block_init(ks[0], cfg)}
    if kind == "rec":
        return {"ln1": _norm_init(cfg),
                "rec": L.rglru_block_init(ks[0], cfg),
                "ln2": _norm_init(cfg),
                "mlp": L.mlp_init(ks[1], cfg.mlp, cfg.d_model, cfg.d_ff,
                                  cfg.param_dtype)}
    attn_init = L.mla_block_init if kind.startswith("mla") else L.attn_block_init
    p = {"ln1": _norm_init(cfg),
         "attn": attn_init(ks[0], cfg),
         "ln2": _norm_init(cfg)}
    if kind in ("attn", "attn_dense_wide", "mla_dense"):
        d_ff = cfg.d_ff_dense if kind in ("attn_dense_wide", "mla_dense") \
            else cfg.d_ff
        p["mlp"] = L.mlp_init(ks[1], cfg.mlp, cfg.d_model, d_ff,
                              cfg.param_dtype)
    else:
        p["moe"] = L.moe_init(ks[1], cfg)
    return p


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.n_layers + 3)
    params: Dict[str, Any] = {}

    if not cfg.embed_stub:
        params["embed"] = (0.02 * jax.random.normal(
            keys[-1], (cfg.vocab_size, cfg.d_model))).astype(cfg.param_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = (0.02 * jax.random.normal(
            keys[-2], (cfg.d_model, cfg.vocab_size))).astype(cfg.param_dtype)
    elif cfg.embed_stub:
        raise ValueError("tie_embeddings requires an input embedding table")
    params["final_norm"] = _norm_init(cfg)

    if cfg.hybrid:
        n_super, n_tail = cfg.n_layers // 3, cfg.n_layers % 3
        supers = []
        for s in range(n_super):
            k3 = jax.random.split(keys[s], 3)
            supers.append({
                "rec1": _layer_init(k3[0], cfg, "rec"),
                "rec2": _layer_init(k3[1], cfg, "rec"),
                "attn": _layer_init(k3[2], cfg, "attn"),
            })
        params["super_blocks"] = _stack(supers)
        params["tail_blocks"] = [
            _layer_init(keys[n_super + t], cfg, "rec") for t in range(n_tail)]
        return params

    kinds = [_layer_kind(cfg, i) for i in range(cfg.n_layers)]
    n_pre = cfg.first_k_dense if cfg.n_experts > 0 else 0
    params["pre_blocks"] = [
        _layer_init(keys[i], cfg, kinds[i]) for i in range(n_pre)]
    params["blocks"] = _stack([
        _layer_init(keys[i], cfg, kinds[i])
        for i in range(n_pre, cfg.n_layers)])
    return params


def init_abstract(cfg: ModelConfig, seed: int = 0):
    """Parameter ShapeDtypeStructs without allocating (for the dry run)."""
    return jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.key(seed))


# ==========================================================================
# Cache initialization
# ==========================================================================

def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    """Decode cache pytree; grouping mirrors the parameter grouping."""
    dt = cfg.dtype

    def one(kind):
        if kind == "ssm":
            return L.mamba2_cache_init(cfg, batch, dt)
        if kind == "rec":
            return L.rglru_cache_init(cfg, batch, dt)
        if cfg.use_mla:
            return L.mla_cache_init(cfg, batch, max_seq, dt)
        return L.attn_cache_init(cfg, batch, max_seq, dt)

    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.hybrid:
        n_super, n_tail = cfg.n_layers // 3, cfg.n_layers % 3
        cache["super_blocks"] = _stack([
            {"rec1": one("rec"), "rec2": one("rec"), "attn": one("attn")}
            for _ in range(n_super)])
        cache["tail_blocks"] = [one("rec") for _ in range(n_tail)]
        return cache

    kinds = [_layer_kind(cfg, i) for i in range(cfg.n_layers)]
    n_pre = cfg.first_k_dense if cfg.n_experts > 0 else 0
    cache["pre_blocks"] = [one(kinds[i]) for i in range(n_pre)]
    cache["blocks"] = _stack([one(kinds[i])
                              for i in range(n_pre, cfg.n_layers)])
    return cache


# ==========================================================================
# Blocks
# ==========================================================================

def _no_aux():
    """A layer's MoE statistics where it has no routed experts."""
    z = jnp.zeros((), f32)
    return {"moe_aux": z, "expert_rows_max": z, "expert_rows_sum": z}


def _add_aux(a, b):
    """Statistics of two layers: losses and row counts add, the largest
    row count is the larger."""
    return {"moe_aux": a["moe_aux"] + b["moe_aux"],
            "expert_rows_max": jnp.maximum(a["expert_rows_max"],
                                           b["expert_rows_max"]),
            "expert_rows_sum": a["expert_rows_sum"] + b["expert_rows_sum"]}


def _residual(cfg, x, out):
    """``x + out``, the branch scaled by ``cfg.residual_multiplier``."""
    if cfg.residual_multiplier != 1.0:
        out = out * jnp.asarray(cfg.residual_multiplier, out.dtype)
    return x + out


def _apply_layer(p, cfg, kind, x, positions, cache, cache_pos,
                 max_seq: int = 0):
    """Pre-norm residual layer.  Returns (x, new_cache, aux), ``aux`` the
    MoE statistics of :func:`layers.moe_apply` (zeros without experts).

    ``cache`` is the decode-time state (None during train/prefill);
    ``max_seq > 0`` marks prefill: attention layers then emit ring-packed
    caches of that size (recurrent layers always emit their final state).
    The sequence mixer runs under the named scope ``mixer``, the MLP or
    MoE under ``mlp``.
    """
    # anchor the residual stream: replicated over the model axis
    x = L.constrain(x, L._U, L._U, None)
    aux = _no_aux()
    if kind == "ssm":
        h = L.apply_norm(p["ln"], x, cfg.norm)
        with jax.named_scope("mixer"):
            out, new_cache = L.mamba2_block_apply(p["mixer"], cfg, h,
                                                  cache=cache)
        return _residual(cfg, x, out), new_cache, aux
    if kind == "rec":
        h = L.apply_norm(p["ln1"], x, cfg.norm)
        with jax.named_scope("mixer"):
            out, new_cache = L.rglru_block_apply(p["rec"], cfg, h,
                                                 cache=cache)
        x = _residual(cfg, x, out)
        h = L.apply_norm(p["ln2"], x, cfg.norm)
        with jax.named_scope("mlp"):
            x = _residual(cfg, x, L.mlp_apply(p["mlp"], cfg.mlp, h))
        return x, new_cache, aux

    h = L.apply_norm(p["ln1"], x, cfg.norm)
    with jax.named_scope("mixer"):
        if kind.startswith("mla"):
            out, new_cache = L.mla_block_apply(
                p["attn"], cfg, h, positions=positions, cache=cache,
                cache_pos=cache_pos, max_seq=max_seq)
        else:
            out, new_cache = L.attn_block_apply(
                p["attn"], cfg, h, positions=positions, cache=cache,
                cache_pos=cache_pos, max_seq=max_seq)
    x = _residual(cfg, x, out)
    h = L.apply_norm(p["ln2"], x, cfg.norm)
    with jax.named_scope("mlp"):
        if "moe" in p:
            out, aux = L.moe_apply(p["moe"], cfg, h)
        else:
            out = L.mlp_apply(p["mlp"], cfg.mlp, h)
    return _residual(cfg, x, out), new_cache, aux


# ==========================================================================
# Forward
# ==========================================================================

def _default_positions(cfg, B, Lq, offset):
    base = jnp.arange(Lq)[None, :] + offset          # (1, L) or (B, L)
    base = jnp.broadcast_to(base, (B, Lq))
    if cfg.pos == "mrope":
        return jnp.broadcast_to(base[None], (3, B, Lq))
    return base


def forward(params, cfg: ModelConfig, inputs, *, positions=None,
            cache=None, mode: str = "train", max_seq: int = 0,
            remat: bool = True):
    """Run the model.

    inputs: tokens (B, L) int32, or embeddings (B, L, d) for stub-frontend
    archs.
    mode:
      * "train"   — full sequence, no cache in or out;
      * "prefill" — full sequence; returns a freshly built decode cache of
        capacity ``max_seq`` (ring-packed for attention layers, final state
        for recurrent layers);
      * "decode"  — L == 1, ``cache`` required, returns the updated cache.

    Returns (logits (B, L, V), new_cache_or_None, aux_dict): ``moe_aux``
    the layers' summed load-balance loss and, with routed experts,
    ``expert_rows_max`` / ``expert_rows_mean``, the largest and the mean
    number of assignments a held expert computed, over layers and held
    experts.
    """
    if mode == "decode" and cache is None:
        raise ValueError("decode needs a cache")
    if mode == "prefill" and max_seq <= 0:
        raise ValueError("prefill needs max_seq")
    if mode != "prefill":
        max_seq = 0
    want_cache = mode in ("prefill", "decode")

    with jax.named_scope("embed"):
        if cfg.embed_stub:
            x = inputs.astype(cfg.dtype)
            B, Lq = x.shape[0], x.shape[1]
        else:
            B, Lq = inputs.shape
            x = params["embed"][inputs].astype(cfg.dtype)
            if cfg.embed_multiplier != 1.0:
                x = x * jnp.asarray(cfg.embed_multiplier, x.dtype)

        cache_pos = (cache["pos"] if cache is not None
                     else jnp.zeros((), jnp.int32))
        if positions is None:
            positions = _default_positions(cfg, B, Lq, cache_pos)
        if cfg.pos == "sinusoidal":
            pos_emb = L.sinusoidal_embedding(
                positions if positions.ndim == 2 else positions[0],
                cfg.d_model)
            x = x + pos_emb.astype(cfg.dtype)

    aux_total = _no_aux()
    new_cache: Optional[Dict[str, Any]] = {} if want_cache else None
    # activation checkpointing: in train mode, each scanned layer saves
    # only its (bf16) input and recomputes internals in the backward pass —
    # the standard memory/compute trade at these batch sizes, and it also
    # prevents XLA from stashing f32 flash-attention internals per layer.
    use_remat = remat and mode == "train"

    def run(p, kind, xc, c):
        return _apply_layer(p, cfg, kind, xc, positions, c, cache_pos,
                            max_seq)

    if cfg.hybrid:
        def super_body(carry, p, c):
            xc, aux = carry
            xc, nc1, a1 = run(p["rec1"], "rec", xc,
                              c["rec1"] if c is not None else None)
            xc, nc2, a2 = run(p["rec2"], "rec", xc,
                              c["rec2"] if c is not None else None)
            xc, nc3, a3 = run(p["attn"], "attn", xc,
                              c["attn"] if c is not None else None)
            return ((xc, _add_aux(_add_aux(_add_aux(aux, a1), a2), a3)),
                    {"rec1": nc1, "rec2": nc2, "attn": nc3})

        if cache is not None:
            fn = lambda carry, xs: super_body(carry, xs[0], xs[1])
            xs = (params["super_blocks"], cache["super_blocks"])
        else:
            fn = lambda carry, xs: super_body(carry, xs, None)
            xs = params["super_blocks"]
        if use_remat:
            fn = jax.checkpoint(fn)
        (x, aux_total), new_super = jax.lax.scan(fn, (x, aux_total), xs)
        new_tail = []
        for t, tp in enumerate(params["tail_blocks"]):
            tc = cache["tail_blocks"][t] if cache is not None else None
            x, ntc, a = run(tp, "rec", x, tc)
            aux_total = _add_aux(aux_total, a)
            new_tail.append(ntc)
        if want_cache:
            new_cache["super_blocks"] = new_super
            new_cache["tail_blocks"] = new_tail
    else:
        kinds = [_layer_kind(cfg, i) for i in range(cfg.n_layers)]
        n_pre = cfg.first_k_dense if cfg.n_experts > 0 else 0
        new_pre = []
        for i in range(n_pre):
            pc = cache["pre_blocks"][i] if cache is not None else None
            x, npc, a = run(params["pre_blocks"][i], kinds[i], x, pc)
            aux_total = _add_aux(aux_total, a)
            new_pre.append(npc)
        kind = kinds[n_pre] if cfg.n_layers > n_pre else "attn"

        def block_body(carry, p, c):
            xc, aux = carry
            xc, nc, a = run(p, kind, xc, c)
            return (xc, _add_aux(aux, a)), nc

        if cache is not None:
            fn = lambda carry, xs: block_body(carry, xs[0], xs[1])
            xs = (params["blocks"], cache["blocks"])
        else:
            fn = lambda carry, xs: block_body(carry, xs, None)
            xs = params["blocks"]
        if use_remat:
            fn = jax.checkpoint(fn)
        (x, aux_total), new_blocks = jax.lax.scan(fn, (x, aux_total), xs)
        if want_cache:
            new_cache["pre_blocks"] = new_pre
            new_cache["blocks"] = new_blocks

    with jax.named_scope("head"):
        x = L.apply_norm(params["final_norm"], x, cfg.norm)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.einsum("bld,dv->blv", x, head.astype(x.dtype),
                            preferred_element_type=f32)
        if cfg.logits_divisor != 1.0:
            logits = logits / cfg.logits_divisor
        logits = L.constrain(logits, L._U, L._U, L._mdl(cfg.vocab_size))

    if want_cache:
        new_cache["pos"] = cache_pos + Lq
    aux = {"moe_aux": aux_total["moe_aux"]}
    if cfg.n_experts > 0:
        n_moe = cfg.n_layers - cfg.first_k_dense
        aux["expert_rows_max"] = aux_total["expert_rows_max"]
        aux["expert_rows_mean"] = aux_total["expert_rows_sum"] / (
            n_moe * len(cfg.held_experts))
    return logits, new_cache, aux


# ==========================================================================
# Loss / train step building blocks
# ==========================================================================

def cross_entropy(logits, targets, mask=None):
    """Mean next-token CE in f32.  logits (B, L, V), targets (B, L).

    The gold logit is extracted with an iota-compare + masked reduction
    rather than ``take_along_axis``: a gather along a vocab axis that is
    sharded over the ``model`` mesh axis would force XLA to all-gather the
    full logits (hundreds of GB at the production shapes); the compare
    form stays elementwise + local-reduce + tiny all-reduce.
    """
    logits = logits.astype(f32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    gold = jnp.where(vocab_iota == targets[..., None], logits, 0.0).sum(-1)
    nll = lse - gold
    if mask is None:
        return nll.mean()
    maskf = mask.astype(f32)
    return (nll * maskf).sum() / jnp.maximum(maskf.sum(), 1.0)


def loss_and_load(params, cfg: ModelConfig, batch, *, positions=None):
    """Next-token LM loss and the held routed experts' load: the row
    counts of :func:`forward` (``expert_rows_max``, ``expert_rows_mean``),
    ``{}`` without routed experts.  batch: {"tokens": (B, L)} or, for stub
    frontends, {"embeds": (B, L, d), "labels": (B, L)}."""
    if cfg.embed_stub:
        inputs, labels = batch["embeds"], batch["labels"]
    else:
        inputs, labels = batch["tokens"], batch["tokens"]
    logits, _, aux = forward(params, cfg, inputs, positions=positions,
                             mode="train")
    with jax.named_scope("head"):
        loss = cross_entropy(logits[:, :-1], labels[:, 1:])
    if cfg.n_experts > 0:
        loss = loss + cfg.router_aux_coef * aux["moe_aux"] / cfg.n_layers
    return loss, {k: v for k, v in aux.items() if k != "moe_aux"}


def loss_fn(params, cfg: ModelConfig, batch, *, positions=None):
    """Next-token LM loss (:func:`loss_and_load` without the load)."""
    return loss_and_load(params, cfg, batch, positions=positions)[0]


def prefill(params, cfg: ModelConfig, inputs, *, max_seq: int,
            positions=None):
    """Process a full prompt, returning (last-token logits, decode cache)."""
    logits, new_cache, _ = forward(params, cfg, inputs, positions=positions,
                                   mode="prefill", max_seq=max_seq)
    return logits[:, -1], new_cache


def decode_step(params, cfg: ModelConfig, token_or_embed, cache, *,
                positions=None):
    """One decode step.  token (B, 1) int32 or embed (B, 1, d)."""
    logits, new_cache, _ = forward(params, cfg, token_or_embed,
                                   positions=positions, cache=cache,
                                   mode="decode")
    return logits[:, -1], new_cache
