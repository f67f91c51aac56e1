"""Host time to issue the batch's host-to-device copy per training
step, over the whole window: the program's ``sched.put`` telemetry
spans (the batch's ``jnp.asarray`` calls and ``_augment_batch``)
summed and divided by its ``sched.dispatch`` spans, one per step. The
copy is asynchronous: what of the transfer is still running when the
span ends shows up in the dispatch or the read-back."""


def read(ctx):
    put = ctx["obs"].get("sched.put", [])
    steps = len(ctx["obs"].get("sched.dispatch", []))
    if not put or not steps:
        return None
    return 1e3 * sum(d for d, _ in put) / steps
