"""Whole-step model FLOP utilisation: the FLOPs of every token the model
processed in the window (each prompt token once, each decoded token once;
the family's ``call_cost``: for a dense decoder, 2 per multiply-add of
the matmuls plus attention over the live context) per second of the
window, over the chip's peak bf16 FLOP/s."""


def read(run):
    if not run.peaks:
        return None
    flops = sum(run.family.call_cost(run.spec, c)[0] for c in run.calls)
    return flops / run.window_s / run.peaks["bf16_flops"] * 100.0
