"""Model / training / mesh configuration dataclasses.

Every assigned architecture gets one module in this package defining
``CONFIG`` (the exact full-scale configuration from the assignment table)
and ``SMOKE`` (a reduced same-family variant: <=2 layers, d_model <= 512,
<=4 experts) used by the CPU smoke tests.  ``repro.configs.get(name)``
resolves either.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                 # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // n_heads
    source: str = ""               # citation from the assignment table

    # normalization / mlp / positional flavor
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    mlp: str = "swiglu"            # swiglu | geglu | gelu
    pos: str = "rope"              # rope | mrope | sinusoidal | none
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0     # stablelm: partial rotary (0.25)
    mrope_sections: Tuple[int, ...] = ()   # qwen2-vl: (16, 24, 24) half-dims
    tie_embeddings: bool = False

    # attention
    attn: str = "full"             # full | sliding
    window: int = 0                # sliding-window size (attn == "sliding")
    attn_logit_softcap: float = 0.0
    attn_scale: float = 0.0        # 0 -> 1 / sqrt(head_dim)

    # Granite's muP multipliers; 1.0 leaves the arithmetic as it is
    embed_multiplier: float = 1.0      # token embeddings times this
    residual_multiplier: float = 1.0   # every residual branch times this
    logits_divisor: float = 1.0        # output logits divided by this

    # modality frontend stub (vlm/audio): inputs are precomputed embeddings
    embed_stub: bool = False

    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    d_expert: int = 0
    first_k_dense: int = 0         # deepseek-v2: first layer(s) use dense FFN
    d_ff_dense: int = 0            # dense-FFN width for those layers
    router_aux_coef: float = 0.01
    # the routed experts this model holds: ``n_held_experts`` of them from
    # ``first_held_expert`` on (one chip's share of an expert-parallel
    # layer); 0 holds all ``n_experts``
    first_held_expert: int = 0
    n_held_experts: int = 0

    # MLA (deepseek-v2)
    use_mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    # SSM (mamba2 / SSD)
    ssm: bool = False
    d_state: int = 0
    d_conv: int = 4
    expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 64
    n_groups: int = 1

    # hybrid (recurrentgemma): layer i is attention iff (i % 3 == 2)
    hybrid: bool = False
    lru_width: int = 0

    # numerics
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.head_dim == 0 and self.n_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def held_experts(self) -> range:
        """Ids of the routed experts whose weights this model holds."""
        n = self.n_held_experts or self.n_experts
        return range(self.first_held_expert, self.first_held_expert + n)

    @property
    def d_inner(self) -> int:        # ssm inner width
        return self.expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for the 524k-token decode shape."""
        return self.ssm or self.hybrid or self.attn == "sliding"

    def param_count(self) -> int:
        """Analytic parameter count (used for roofline MODEL_FLOPS)."""
        import math
        from repro.models.transformer import init_abstract
        import jax
        shapes = init_abstract(self)
        return sum(math.prod(l.shape)
                   for l in jax.tree_util.tree_leaves(shapes))

    def active_param_count(self) -> int:
        """Activated parameters per token (MoE: routed top_k of n_experts,
        of the held ones in expectation; shared experts and everything
        else fully active)."""
        if self.n_experts == 0:
            return self.param_count()
        import math
        from repro.models.transformer import init_abstract
        import jax
        shapes = init_abstract(self)
        total = 0
        routed = ("w_gate", "w_up", "w_down")
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
            n = math.prod(leaf.shape)
            keys = [str(getattr(p, "key", p)) for p in path]
            if "moe" in keys and keys[-1] in routed and "shared" not in keys:
                n = n * self.top_k // self.n_experts
            total += n
        return total


@dataclasses.dataclass(frozen=True)
class InputShape:
    """One of the four assigned (seq_len, global_batch, kind) tuples."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 0.1
    momentum: float = 0.0
    weight_decay: float = 0.0
    optimizer: str = "sgd"          # sgd | adam
    warmup_steps: int = 0
    schedule: str = "constant"      # constant | cosine
    total_steps: int = 1000
    grad_clip: float = 0.0
    seed: int = 0
