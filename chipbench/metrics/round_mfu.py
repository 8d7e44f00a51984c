"""Model FLOP utilisation of the traced window: 3 x the forward FLOPs of
one sample (counted from the configuration's reference model by
``chipbench/flops.py``) times the samples the rounds' plans train (the
planned batch times tau of every participant, not the padded tiers) over
the traced window (the span of the device's work in it) times the chip's
bf16 peak."""

from chipbench import kernels as K


def read(ctx):
    samples = ctx["planned_samples"]
    if not samples:
        return None
    peaks = K.peaks(ctx["device"]["kind"])
    flops = 3.0 * ctx["forward_flops"] * samples
    return 100.0 * flops / (ctx["trace"]["window_s"]
                            * ctx["device"]["count_used"]
                            * peaks["bf16_flops_per_s"])
