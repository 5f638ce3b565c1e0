"""Pallas TPU kernels for the compute hot-spots (DESIGN.md §5):

  * ``safeguard_filter`` — the master's O(m^2 d) pairwise-distance pass
    over the flat ``(m, d_pad)`` accumulator buffer (DESIGN.md §6),
    d-tiled through VMEM with MXU rank-k Gram updates; ships both the
    plain Gram/distance kernel and the fused variant that applies the
    windowed accumulate-and-reset in place (``input_output_aliases``)
    while streaming each tile exactly once;
  * ``flash_attention``  — the train/prefill path's causal GQA attention
    on the TPU (``flash_attention_train``: JAX's splash forward, dq and
    dkv kernels).

Each package ships ``ops.py`` (jit-able wrapper with padding/tile
choice/dispatch) and ``ref.py`` (pure-jnp oracle); ``safeguard_filter``
also its own ``kernel.py`` (pl.pallas_call + BlockSpec).  Kernels are
validated on CPU with ``interpret=True`` against the oracle; TPU is the
compiled target.
"""
