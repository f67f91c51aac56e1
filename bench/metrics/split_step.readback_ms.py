"""Host time of one read-back of the step's loss (``float(loss)``), over
the whole window: the mean of the program's ``sched.readback``
telemetry spans, the host waiting for the device to finish the step."""


def read(ctx):
    spans = ctx["obs"].get("sched.readback", [])
    if not spans:
        return None
    return 1e3 * sum(d for d, _ in spans) / len(spans)
