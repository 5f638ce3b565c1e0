"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of the device's operation intervals / window)."""


def read(ctx):
    red = ctx["trace"]
    if not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
