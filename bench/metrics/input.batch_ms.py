"""Host time spent in ``Batcher.batch_at`` per training step, over the
whole window: the harness's ``batch`` spans (one per client batch)
summed and divided by the window's ``step`` spans."""


def read(ctx):
    batch = ctx["spans"].get("batch", [])
    steps = len(ctx["spans"].get("step", []))
    if not batch or not steps:
        return None
    return 1e3 * sum(batch) / steps
