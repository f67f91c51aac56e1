"""Host time of one migration's host-to-device copy of the restored
state at the destination edge, over the whole window: the mean of the
program's ``sched.restore`` telemetry spans, nested in ``sched.move``."""


def read(ctx):
    spans = ctx["obs"].get("sched.restore", [])
    if not spans:
        return None
    return 1e3 * sum(d for d, _ in spans) / len(spans)
