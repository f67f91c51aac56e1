"""Host time of one migration's device-to-host copy of the checkpoint
(server parameters, optimizer state, last gradients), over the whole
window: the mean of the program's ``mig.fetch`` telemetry spans,
nested in ``mig.pack``."""


def read(ctx):
    spans = ctx["obs"].get("mig.fetch", [])
    if not spans:
        return None
    return 1e3 * sum(d for d, _ in spans) / len(spans)
