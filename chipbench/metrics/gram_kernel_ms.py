"""Device time per step of the safeguard's Pallas Gram kernel
(``kernels.safeguard_filter``), in ms: the trace's events of the kernel,
found by its name, over the steps of the traced window."""

KERNELS = ("pairwise_sqdist_kernel",)


def read(ctx):
    ops = ctx["trace"]["ops"]
    seconds = sum(v for k, v in ops.items() if k.split(".")[0] in KERNELS)
    if not seconds or not ctx["host"]["steps"]:
        return None
    return 1e3 * seconds / ctx["host"]["steps"]
