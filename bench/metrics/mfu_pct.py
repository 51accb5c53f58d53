"""Whole-step model FLOP utilisation: the FLOPs of every token the model
processed in the window (each replayed prompt token once, each decoded
token once; 2 per multiply-add of the matmuls plus attention over the
live context, `model.decode_call_cost`) per second of the window, over
the chip's peak bf16 FLOP/s."""

from benchkit.model import decode_call_cost


def read(run):
    if not run.peaks:
        return None
    flops = sum(decode_call_cost(run.spec, c.contexts)[0] for c in run.calls)
    return flops / run.window_s / run.peaks["bf16_flops"] * 100.0
