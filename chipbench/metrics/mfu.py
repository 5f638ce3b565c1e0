"""Whole step's share of the chips' bf16 peak, in %: the step's model FLOPs
(``chipbench/flops.py``) times the steps of the traced window, over the
window's seconds times chips times the peak of ``chipbench/peaks.json``."""


def read(ctx):
    red, host = ctx["trace"], ctx["host"]
    if not host["steps"] or not red["window_s"]:
        return None
    flops = ctx["step_flops"] * host["steps"]
    return 100.0 * flops / (red["window_s"] * ctx["chips"]
                            * ctx["peaks"]["bf16_flops_per_s"])
