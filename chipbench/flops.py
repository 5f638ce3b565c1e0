"""Operations and bytes that a step requires, from the configuration's
shapes alone, so the count stays the same whatever implements the step.

Model FLOPs of training count the forward and backward passes once, with
no recomputation: 6 FLOPs per matrix-multiply parameter per token, plus
the sequence mixer's own.  Both counts belong to the architecture and
live beside its reference (``reference/<model_type>.py``:
``matmul_params`` and ``mixer_flops_per_token``), so that a new
architecture brings its count in its own file.
"""

from __future__ import annotations

from chipbench import bench


def tokens_per_step(traffic: dict) -> int:
    return traffic["workers"] * traffic["batch_per_worker"] * traffic["seq_len"]


def step_flops(model: dict, traffic: dict) -> float:
    ref = bench.reference(model["model_type"])
    per_token = (6.0 * ref.matmul_params(model)
                 + ref.mixer_flops_per_token(model, traffic["seq_len"]))
    return per_token * tokens_per_step(traffic)


def gram_bytes(param_count: int, traffic: dict) -> float:
    """Bytes the safeguard's distance pass must read per step: both f32
    accumulators, ``(m, d)`` each with ``d`` the unpadded parameter count,
    once."""
    return 2.0 * traffic["workers"] * param_count * 4
