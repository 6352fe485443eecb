"""decode_attn_roofline: the least time the chip could take for the decode
attention of the traced steps (the larger of FLOPs over peak and bytes over
bandwidth, counted from the true context lengths, ``counts``) over the
device time of the decode attention kernels, in %. Layer: kernels
(``kernels/lean_decode.py``).

The program's Pallas calls carry no name the trace keeps: on the TPU's op
line each is a ``custom-call`` to ``tpu_custom_call`` named only by its
instruction id. In a decode-only window the paged stream-K decode kernels,
fused or two-phase with its merge, are the only such calls, so every one of
them is counted."""
from bench.harness import counts, tracing

KERNELS = ('custom_call_target="tpu_custom_call"',)


def read(record):
    if record.trace is None or not record.traced_ticks:
        return None
    lo, hi = record.trace_window
    took = sum(e.seconds for e in tracing.clip(record.trace.ops, lo, hi)
               if tracing.matches(e.name, KERNELS))
    if took <= 0:
        return None
    layers = int(record.cfg["num_hidden_layers"])
    least = layers * sum(
        counts.roofline_seconds(*counts.decode_attention(record.cfg, ctx), record.peaks)
        for ctx in record.traced_ticks
    )
    return 100.0 * least / took
