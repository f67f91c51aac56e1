"""Mean duration of the program's own ``mig.pack`` telemetry spans
(checkpoint to FFLY bytes, the quantize included) in the window."""


def read(ctx):
    spans = ctx["obs"].get("mig.pack", [])
    if not spans:
        return None
    return 1e3 * sum(d for d, _ in spans) / len(spans)
