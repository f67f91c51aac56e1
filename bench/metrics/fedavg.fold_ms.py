"""Host time of one aggregation call over the window: the harness's
``aggregate`` spans around ``FedFlyScheduler._aggregate``."""


def read(ctx):
    spans = ctx["spans"].get("aggregate", [])
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
