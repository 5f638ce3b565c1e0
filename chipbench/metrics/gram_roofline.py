"""The Gram kernel's share of its roofline, in %: the bytes the distance
pass requires (both f32 accumulators read once, ``chipbench/flops.py``)
over the HBM bandwidth of ``chipbench/peaks.json`` times the kernel's
device time per step (``pairwise_sqdist_kernel`` events, as
``gram_kernel_ms`` reads them).  Memory bounds it: per column the pass
reads ``8 m`` bytes for ``2 m^2`` FLOPs."""

KERNELS = ("pairwise_sqdist_kernel",)


def read(ctx):
    ops = ctx["trace"]["ops"]
    seconds = sum(v for k, v in ops.items() if k.split(".")[0] in KERNELS)
    if not seconds or not ctx["host"]["steps"]:
        return None
    per_step = seconds / ctx["host"]["steps"]
    return 100.0 * ctx["gram_bytes"] / (ctx["peaks"]["hbm_bytes_per_s"]
                                         * per_step)
