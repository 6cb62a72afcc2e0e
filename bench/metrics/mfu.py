"""Model step: model FLOPs of the work both tiers did in the window (two
per weight each live token multiplied through, attention over its
context, the output projection where its logits were used) over the
window's length times the chips times the chips' bf16 peak, in percent."""


def read(run):
    return 100.0 * run.model_flops() / (
        run.window_s * run.chips * run.peak.bf16_flops)
