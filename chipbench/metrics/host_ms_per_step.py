"""Host time per step of the measured loop (``train.trainer.Trainer.run``
and the benchmark's wrappers), outside the waits on the device, in ms:
host clock of the step wrapper over the traced window."""


def read(ctx):
    host = ctx["host"]
    if not host["steps"]:
        return None
    return 1e3 * (host["window_s"] - host["blocked_s"]) / host["steps"]
