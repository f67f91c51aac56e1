"""Compile time per migration in the window: the program's
``jit.compile`` telemetry spans summed and divided by its ``sched.move``
spans. A ``jit.compile`` span is JAX's backend-compile event: the XLA
compile, or the load from the persistent compilation cache that stands
in for it. It leaves out tracing and lowering, and it counts every
compile in the window, not only those inside a move. Set-up warms every
program the window uses, so a sound run reads 0; the codec's kernels
compiling again on every move read above it."""


def read(ctx):
    moves = len(ctx["obs"].get("sched.move", []))
    if not moves:
        return None
    return 1e3 * sum(d for d, _ in ctx["obs"].get("jit.compile", [])) / moves
