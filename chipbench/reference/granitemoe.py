"""Plain float32 reference of GraniteMoE (hf:ibm-granite/granite-3.0-*-base,
``model_type`` ``granitemoe``), for one chip's share of the experts.

Each layer: RMSNorm, grouped-query attention with rotary positions (the
halves of each head rotated, llama's convention) and the published scale
``attention_multiplier`` in place of ``1/sqrt(head_dim)``; RMSNorm, then
the routed SwiGLU experts: the router scores all ``num_experts_routed``
(the published ``num_local_experts``), each token takes the top
``num_experts_per_tok`` logits and a softmax over them.  Both branches join the residual times ``residual_multiplier``.  The
embeddings are multiplied by ``embedding_multiplier``; the head is the
embedding, tied, and its logits are divided by ``logits_scaling``.  The
loss is the next-token cross-entropy plus ``router_aux_loss_coef`` times
the layers' mean Switch load-balance loss, ``E * sum_e f_e P_e`` over all
``E`` experts (``f_e`` the share of the layer's assignments that chose
expert ``e``, ``P_e`` its mean router probability), as the program counts
it.

Departures from the published model, each as the system under test runs
it:

* the experts are one chip's share: ``num_local_experts`` of them from
  ``first_expert_held`` on.  The MoE output is computed densely, every held
  expert on every token times the token's routing weight for it (zero
  where the expert is not among its top ``k``), so it depends on no sort
  or dispatch; what the other experts would add is left out;
* the vocabulary is the configuration's (a slice of the published one),
  and the cross-entropy runs over it;
* the norms' weights are stored as offsets from one (``x * (1 + w)``);
* ``init`` draws the weights from the run's seed key by the run's scheme
  (0.02-scaled normals in the stored dtype, the router rounded to it and
  kept in float32, norms zero).

Attention is computed one key/value head group at a time, each group's
full causal score matrix at once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import refops

f32 = jnp.float32


def _dims(model):
    d = model["hidden_size"]
    H = model["num_attention_heads"]
    return (d, H, model["num_key_value_heads"], d // H,
            model["intermediate_size"], model["num_experts_routed"],
            model["num_experts_per_tok"], model["num_local_experts"],
            model["vocab_size"], model["num_hidden_layers"])


def matmul_params(model: dict) -> int:
    """Parameters of the matrix multiplications one token goes through:
    per layer the attention projections, the router and the held experts'
    expected share (``k * G / E`` experts of three matrices each), and the
    tied head."""
    d, H, K, Dh, f, E, k, G, V, n = _dims(model)
    attn = 2 * d * H * Dh + 2 * d * K * Dh
    experts = 3 * d * f * k * G / E
    return int(n * (attn + d * E + experts) + d * V)


def mixer_flops_per_token(model: dict, seq_len: int) -> float:
    """Causal attention's FLOPs per token of training at ``seq_len``: per
    layer ``q k^T`` and ``p v``, ``2 * 2 * H * Dh`` each per key, over
    ``seq_len / 2`` keys on average, times 3 for forward and backward."""
    d, H, K, Dh, f, E, k, G, V, n = _dims(model)
    return 3.0 * n * 4 * H * Dh * seq_len / 2


def init(model: dict, key):
    d, H, K, Dh, f, E, k, G, V, n = _dims(model)
    dt = refops.dtype_of(model)
    normal = lambda key, shape: refops.normal(key, shape, dt)
    keys = jax.random.split(key, n + 3)

    def layer(key):
        ks = jax.random.split(key, 4)
        ka = jax.random.split(ks[0], 4)
        km = jax.random.split(ks[1], 5)
        return {"ln1": {"scale": jnp.zeros((d,), dt)},
                "attn": {"wq": normal(ka[0], (d, H * Dh)),
                         "wk": normal(ka[1], (d, K * Dh)),
                         "wv": normal(ka[2], (d, K * Dh)),
                         "wo": normal(ka[3], (H * Dh, d))},
                "ln2": {"scale": jnp.zeros((d,), dt)},
                "moe": {"router": normal(km[0], (d, E)).astype(f32),
                        "w_gate": normal(km[1], (G, d, f)),
                        "w_up": normal(km[2], (G, d, f)),
                        "w_down": normal(km[3], (G, f, d))}}

    layers = [layer(keys[i]) for i in range(n)]
    return {"blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *layers),
            "embed": normal(keys[-1], (V, d)),
            "final_norm": {"scale": jnp.zeros((d,), dt)}}


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    """Rotate each head's halves by the position's angles: ``x`` (b, L,
    heads, Dh)."""
    L, Dh = x.shape[1], x.shape[-1]
    half = Dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=f32) / half)
    ang = jnp.arange(L, dtype=f32)[:, None] * freqs          # (L, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss(params, tokens, model: dict, *, precision: str = "f32",
         half: bool = False):
    """Next-token loss of one worker's ``tokens`` (b, L), plus the
    router's auxiliary loss."""
    d, H, K, Dh, f, E, k, G, V, n = _dims(model)
    first = model["first_expert_held"]
    eps = model["rms_norm_eps"]
    res = model["residual_multiplier"]
    ein = refops.einsum(precision)
    p = jax.tree.map(lambda a: a.astype(f32), params)
    b, L = tokens.shape
    causal = jnp.tril(jnp.ones((L, L), bool))

    @jax.checkpoint
    def head_group(args):
        q, kk, v = args                     # (b, L, H/K, Dh), (b, L, Dh) x 2
        s = ein("blgh,bsh->bgls", q, kk) * model["attention_multiplier"]
        s = jnp.where(causal, s, -jnp.inf)
        return ein("bgls,bsh->blgh", jax.nn.softmax(s, axis=-1), v)

    def block(carry, w):
        x, aux = carry
        h = _rms_norm(x, w["ln1"]["scale"], eps)
        q = _rope(ein("bld,df->blf", h, w["attn"]["wq"]).reshape(b, L, H, Dh),
                  model["rope_theta"])
        kk = _rope(ein("bld,df->blf", h, w["attn"]["wk"]).reshape(b, L, K, Dh),
                   model["rope_theta"])
        v = ein("bld,df->blf", h, w["attn"]["wv"]).reshape(b, L, K, Dh)
        q = jnp.moveaxis(q.reshape(b, L, K, H // K, Dh), 2, 0)
        o = jax.lax.map(head_group, (q, jnp.moveaxis(kk, 2, 0),
                                     jnp.moveaxis(v, 2, 0)))
        o = jnp.moveaxis(o, 0, 2).reshape(b, L, H * Dh)
        x = x + res * ein("blf,fd->bld", o, w["attn"]["wo"])

        h = _rms_norm(x, w["ln2"]["scale"], eps)
        m = w["moe"]
        logits = ein("bld,de->ble", h, m["router"])
        top_l, top_e = jax.lax.top_k(logits, k)
        gates = jax.nn.softmax(top_l, axis=-1)
        held = first + jnp.arange(G)
        route = (gates[..., None] * (top_e[..., None] == held)).sum(-2)
        act = (jax.nn.silu(ein("bld,gdf->blgf", h, m["w_gate"]))
               * ein("bld,gdf->blgf", h, m["w_up"]))
        x = x + res * ein("blgf,gfd->bld", act * route[..., None],
                          m["w_down"])
        probs = jax.nn.softmax(logits, axis=-1).reshape(b * L, E).mean(0)
        chosen = (top_e.reshape(b * L * k, 1) == jnp.arange(E)).mean(0)
        return (x, aux + E * jnp.sum(probs * chosen)), None

    x = p["embed"][tokens] * model["embedding_multiplier"]
    (x, aux), _ = jax.lax.scan(jax.checkpoint(block),
                               (x, jnp.zeros((), f32)), p["blocks"])
    x = _rms_norm(x, p["final_norm"]["scale"], eps)
    logits = ein("bld,vd->blv", x, p["embed"]) / model["logits_scaling"]
    return (refops.next_token_loss(logits, tokens, half=half)
            + model["router_aux_loss_coef"] * aux / n)
