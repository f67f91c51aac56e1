"""Share of its roofline that the packed int8 quantize kernel reaches in
the traced slice.

The kernel is bound by memory: per call it reads the flat payload and
its base (residual mode) and writes int8 codes and one float32 scale per
block of 1024, over a buffer padded to whole (8, 1024) tiles; its
operations are negligible beside the bytes. The payload length comes
from the program's ``mig.quantize`` spans (attribute ``n``). The least
time is the bytes over the chip's HBM bandwidth; the share is that over
the summed device time of the kernel's events: the Mosaic custom calls
that return int8 codes (``(s8[...], f32[...]) custom-call``; the
dequantize returns float32)."""

BLOCK, ROWS = 1024, 8


def bytes_per_call(n: int, residual: bool = True) -> int:
    n_pad = -(-n // (ROWS * BLOCK)) * ROWS * BLOCK
    reads = n_pad * 4 * (2 if residual else 1)
    writes = n_pad + (n_pad // BLOCK) * 4
    return reads + writes


def is_quantize(op: str) -> bool:
    _, _, rhs = op.partition(" = ")
    return rhs.startswith("(s8[") and "custom-call(" in rhs


def read(ctx):
    tr, peak = ctx["trace"], ctx["peak"]
    calls = ctx["obs"].get("mig.quantize", [])
    if not tr or not peak or not calls:
        return None
    seconds = sum(t for op, t in tr["ops"].items() if is_quantize(op))
    n_runs = sum(c for op, c in tr["op_counts"].items() if is_quantize(op))
    if seconds <= 0 or not n_runs:
        return None
    sizes = sorted(int(a.get("n", 0)) for _, a in calls)
    n = sizes[len(sizes) // 2]
    least = n_runs * bytes_per_call(n) / peak["hbm_bytes_per_s"]
    return 100.0 * least / seconds
