"""Pallas TPU kernels for the compute hot-spots (DESIGN.md §5):

  * ``safeguard_filter`` — the master's O(m^2 d) pairwise-distance pass
    over the flat ``(m, d_pad)`` accumulator buffer (DESIGN.md §6),
    d-tiled through VMEM with MXU rank-k Gram updates; ships both the
    plain Gram/distance kernel and the fully fused variant that applies
    the windowed accumulate-and-reset in place (``input_output_aliases``)
    while streaming each tile exactly once;
  * ``robust_agg``       — coordinate-wise median / trimmed-mean baselines
    (VPU sorting networks over the worker axis, d-tiled);
  * ``flash_attention``  — the train/prefill path's causal GQA attention
    on the TPU (``flash_attention_train``: JAX's splash forward, dq and
    dkv kernels), and a forward-only causal (+sliding-window, +GQA)
    blocked online-softmax kernel.

Each package ships ``kernel.py`` (pl.pallas_call + BlockSpec), ``ops.py``
(jit-able wrapper with padding/tile choice/dispatch) and ``ref.py``
(pure-jnp oracle).  Kernels are validated on CPU with ``interpret=True``
against the oracle; TPU is the compiled target.
"""
