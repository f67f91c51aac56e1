"""Mean duration of the program's own ``mig.unpack`` telemetry spans
(FFLY bytes back to a checkpoint, the dequantize included) in the
window."""


def read(ctx):
    spans = ctx["obs"].get("mig.unpack", [])
    if not spans:
        return None
    return 1e3 * sum(d for d, _ in spans) / len(spans)
