"""decode_step_mfu: model FLOPs of the traced decode steps over the device
time of the programs that ran them and the chip's bf16 peak, in %. FLOPs per
step: 2 per weight (every layer and the unembedding) per sequence, plus
attention over each sequence's true context in every layer (``counts``).
Layer: the model step (``models/transformer.py`` ``decode_step``).

The engine's jitted decode step reaches the trace as ``jit__unknown``, a
name nothing guarantees, so the time is that of every XLA program in the
traced window: in a decode-only window those are the decode step and the
small programs that take its tokens."""
from bench.harness import counts, tracing


def read(record):
    if record.trace is None or not record.traced_ticks:
        return None
    lo, hi = record.trace_window
    seconds = sum(e.seconds for e in tracing.clip(record.trace.modules, lo, hi))
    if seconds <= 0:
        return None
    flops = sum(counts.decode_step_flops(record.cfg, ctx)
                for ctx in record.traced_ticks)
    return 100.0 * flops / seconds / record.peaks.bf16_flops_per_s
