"""Whole-step share of the chip's peak: training operations per sample
counted from the configuration's shapes (three forward passes), times
the samples trained per second in the traced run's window outside the
profiled slice (the profiler slows the host loop inside it), over the
bf16 peak from ``bench/peaks.json``. The model's float32 convolutions
and products run at default precision, one bf16 pass on a TPU, so bf16
is its peak."""


def read(ctx):
    rate, peak = ctx.get("rest_samples_per_s"), ctx["peak"]
    if not rate or not peak:
        return None
    return 100.0 * ctx["train_flops_per_sample"] * rate \
        / peak["bf16_flops_per_s"]
