"""Whole-step model FLOP/s utilisation: the useful model operations of the
window (trunk forward of every prompt and served token, causal attention,
the head's 2·V·d per served token; ``flops.model_flops``) over the window
times the chip's bf16 peak."""
import flops


def read(run):
    if not run.peaks or run.window_s <= 0:
        return None
    f = flops.model_flops(run.sizes, run.prefills, run.decodes)
    if f <= 0:
        return None
    return 100.0 * f / (run.window_s * float(run.peaks["bf16_flops_per_s"]))
