"""Host time of one dispatch of the jitted split step, over the whole
window: the mean of the program's ``sched.dispatch`` telemetry spans.
The dispatch returns before the device has run the step."""


def read(ctx):
    spans = ctx["obs"].get("sched.dispatch", [])
    if not spans:
        return None
    return 1e3 * sum(d for d, _ in spans) / len(spans)
