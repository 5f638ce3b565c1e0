"""Plain float32 reference of Mamba-2 (arXiv:2405.21060).

Each layer: RMSNorm, then the Mamba-2 mixer: one input projection to
``(z, x, B, C, dt)``, a causal depthwise convolution over ``(x, B, C)``
followed by SiLU, the selective state space with scalar decay per head,
``y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < r <= t} dt_r A) dt_s x_s
+ D x_t``, gated RMSNorm ``norm(y * silu(z))`` and the output projection.
The state space is computed in its quadratic "dual" form over the whole
sequence (the paper's masked-attention form), not by the chunked scan the
system under test runs.  Tied input embedding and output head.

The norms' weights are stored as offsets from one (``x * (1 + w)``) and
their epsilon is the configuration file's ``rms_norm_eps``: both as the
system under test stores and runs them.  ``init`` draws the weights from
the run's seed key by the scheme the run uses (0.02-scaled normals in the stored
dtype; ``A_log = log(1..heads)``, ``D = 1``, ``dt_bias`` the inverse
softplus of a log-uniform draw in [1e-3, 1e-1]).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import refops

f32 = jnp.float32


def _dims(model):
    d = model["hidden_size"]
    di = model["expand"] * d
    P = model["head_dim"]
    return (d, di, di // P, P, model["n_groups"], model["state_size"],
            model["conv_kernel"], model["vocab_size"],
            model["num_hidden_layers"])


def matmul_params(model: dict) -> int:
    """Parameters of the matrix multiplications: the input and output
    projections of each layer and the tied output head."""
    d, di, h, P, g, N, W, V, n = _dims(model)
    return n * (d * (2 * di + 2 * g * N + h) + di * d) + d * V


def mixer_flops_per_token(model: dict, seq_len: int) -> float:
    """The state space's FLOPs per token of training, in its chunked form
    at the configuration's chunk size ``Q``: per layer, forward ``2 Q N``
    for ``C B^T`` per group, ``2 Q P H`` for the masked mixing and ``4 N P
    H`` for the chunk states in and out, times 3 for forward and
    backward."""
    d, di, h, P, g, N, W, V, n = _dims(model)
    Q = min(model["chunk_size"], seq_len)
    return 3.0 * n * (2 * Q * N * g + 2 * Q * P * h + 4 * N * P * h)


def init(model: dict, key):
    d, di, h, P, g, N, W, V, n = _dims(model)
    dt = refops.dtype_of(model)
    normal = lambda k, shape: refops.normal(k, shape, dt)
    keys = jax.random.split(key, n + 3)
    convw = di + 2 * g * N

    def layer(key):
        ks = jax.random.split(key, 4)
        km = jax.random.split(ks[0], 4)
        dt_bias = jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
            km[2], (h,), f32, jnp.log(1e-3), jnp.log(1e-1)))))
        return {"ln": {"scale": jnp.zeros((d,), dt)},
                "mixer": {"in_proj": normal(km[0], (d, 2 * di + 2 * g * N + h)),
                          "conv_w": normal(km[1], (W, convw)),
                          "conv_b": jnp.zeros((convw,), dt),
                          "A_log": jnp.log(jnp.arange(1, h + 1, dtype=f32)),
                          "D": jnp.ones((h,), f32),
                          "dt_bias": dt_bias,
                          "norm_scale": jnp.zeros((di,), dt),
                          "out_proj": normal(km[3], (di, d))}}

    layers = [layer(keys[i]) for i in range(n)]
    return {"blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *layers),
            "embed": normal(keys[-1], (V, d)),
            "final_norm": {"scale": jnp.zeros((d,), dt)}}


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + w)


def loss(params, tokens, model: dict, *, precision: str = "f32",
         half: bool = False):
    """Next-token loss of one worker's ``tokens`` (b, L)."""
    d, di, h, P, g, N, W, V, n = _dims(model)
    eps = model["rms_norm_eps"]
    ein = refops.einsum(precision)
    p = jax.tree.map(lambda a: a.astype(f32), params)
    b, L = tokens.shape
    causal = jnp.tril(jnp.ones((L, L), bool))

    def block(x, w):
        w_ln, w = w["ln"], w["mixer"]
        u = ein("bld,df->blf", _rms_norm(x, w_ln["scale"], eps), w["in_proj"])
        z, xin, Bm, Cm, dt = jnp.split(
            u, [di, 2 * di, 2 * di + g * N, 2 * di + 2 * g * N], axis=-1)
        c = jnp.concatenate([xin, Bm, Cm], axis=-1)
        cp = jnp.pad(c, ((0, 0), (W - 1, 0), (0, 0)))
        c = sum(cp[:, i:i + L] * w["conv_w"][i] for i in range(W))
        c = jax.nn.silu(c + w["conv_b"])
        xs, Bm, Cm = jnp.split(c, [di, di + g * N], axis=-1)
        xs = xs.reshape(b, L, h, P)
        Bh = jnp.repeat(Bm.reshape(b, L, g, N), h // g, axis=2)
        Ch = jnp.repeat(Cm.reshape(b, L, g, N), h // g, axis=2)
        dt = jax.nn.softplus(dt + w["dt_bias"])                 # (b, L, h)
        A = -jnp.exp(w["A_log"])                                # (h,)
        cs = jnp.cumsum(dt * A, axis=1)                         # (b, L, h)
        seg = jnp.moveaxis(cs, 2, 1)                            # (b, h, L)
        seg = seg[:, :, :, None] - seg[:, :, None, :]           # (b, h, t, s)
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        cb = ein("bthn,bshn->bhts", Ch, Bh)
        mix = cb * decay * jnp.moveaxis(dt, 2, 1)[:, :, None, :]
        y = ein("bhts,bshp->bthp", mix, xs) + xs * w["D"][:, None]
        y = y.reshape(b, L, di) * jax.nn.silu(z)
        y = _rms_norm(y, w["norm_scale"], eps)
        return x + ein("blf,fd->bld", y, w["out_proj"]), None

    x = p["embed"][tokens]
    x, _ = jax.lax.scan(jax.checkpoint(block), x, p["blocks"])
    x = _rms_norm(x, p["final_norm"]["scale"], eps)
    logits = ein("bld,vd->blv", x, p["embed"])
    return refops.next_token_loss(logits, tokens, half=half)
