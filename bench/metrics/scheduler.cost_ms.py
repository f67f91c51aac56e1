"""Host time per training step of the scheduler's simulated testbed
clock (``StageCostModel.costs`` and ``batch_time_s``), over the whole
window: the mean of the program's ``sched.cost`` telemetry spans, one
per step."""


def read(ctx):
    spans = ctx["obs"].get("sched.cost", [])
    if not spans:
        return None
    return 1e3 * sum(d for d, _ in spans) / len(spans)
