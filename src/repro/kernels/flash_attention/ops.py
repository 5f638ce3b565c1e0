"""``flash_attention_train``: causal GQA attention of the train/prefill
path, forward and backward as Pallas TPU kernels (JAX's
``splash_attention``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

# Block sizes of the train kernels, chosen on a v5e at the Granite cell's
# attention (4 workers of 4096 tokens, 24 query and 8 KV heads of 64;
# PERF.md §6): (block_q, block_kv, block_kv_compute) of the forward and of
# the fused backward (one dkv kernel that also forms dq).  A sequence
# shorter than a block takes one block of its own length.
FWD_BLOCKS = (1024, 1024, 512)
BWD_BLOCKS = (1024, 1024, 1024)
LANES = 128          # splash computes key blocks in multiples of this


def _blocks(L: int) -> splash.BlockSizes:
    f, b = ([min(x, L) for x in blocks] for blocks in (FWD_BLOCKS, BWD_BLOCKS))
    return splash.BlockSizes(
        block_q=f[0], block_kv=f[1], block_kv_compute=f[2],
        block_q_dkv=b[0], block_kv_dkv=b[1], block_kv_dkv_compute=b[2],
        use_fused_bwd_kernel=True)


def train_kernel_fits(L: int) -> bool:
    """Whether ``flash_attention_train`` takes a sequence of ``L``: every
    block divides it and is a whole number of lane tiles."""
    return all(L % min(b, L) == 0 and min(b, L) % LANES == 0
               for b in FWD_BLOCKS + BWD_BLOCKS)


def flash_attention_train(q, k, v, *, scale: float, interpret: bool = False):
    """Causal GQA attention with Pallas forward, dq and dkv kernels.

    q: (B, L, H, D);  k, v: (B, L, K, D) with ``H % K == 0`` and
    ``train_kernel_fits(L)``.  Returns (B, L, H * D) in q's dtype.

    Splash's MHA form maps each query head onto its KV head in its index
    maps (K/V are never expanded) and skips the key blocks wholly above
    the diagonal.  The score and backward matmuls take the inputs' dtype;
    the forward's probability-value product, the softmax statistics and
    every accumulator are f32.  Splash applies no scale, so ``scale`` is
    folded into q: exact for a power of two (Granite's 1/64, 1/sqrt(64)),
    else one more rounding of q (in bf16 still within the errors of
    ``flash_attention_jnp``).  The kernel is built per trace, and splash
    caches the mask's block map per shape.  ``vmap`` batches it over B,
    and again over any mapped axis around this call.
    """
    B, L, H, D = q.shape
    kernel = splash.make_splash_mha(
        splash.MultiHeadMask([splash.CausalMask((L, L))] * H),
        block_sizes=_blocks(L), head_shards=1, q_seq_shards=1,
        interpret=interpret)
    heads_major = lambda x: jnp.swapaxes(x, 1, 2)            # (B, ·, L, D)

    # Checkpointed: the backward runs the forward kernel again rather than
    # keep its output and its lane-wide f32 logsumexp (H·L·128 per row of
    # B) live through the rest of the layer's backward, where they raised
    # the Granite step's peak above the jnp path's (PERF.md §6).
    @jax.checkpoint
    def attend(q, k, v):
        out = jax.vmap(kernel)(heads_major(q * jnp.asarray(scale, q.dtype)),
                               heads_major(k), heads_major(v))
        return heads_major(out).reshape(B, L, H * D)

    return attend(q, k, v)
