"""Read-backs of the step's loss per training step, over the whole
window: the program's ``sched.readback`` spans over its
``sched.dispatch`` spans, one per step. The scheduler reads the loss
only where the protocol needs it, so each read is a step the host waited
for."""


def read(ctx):
    reads = len(ctx["obs"].get("sched.readback", []))
    steps = len(ctx["obs"].get("sched.dispatch", []))
    if not reads or not steps:
        return None
    return reads / steps
