"""decode_kernel_roofline: ``decode_attn_roofline`` over the decode kernels
alone, in %. Layer: kernels (``kernels/lean_decode.py``).

Each Pallas call passes ``name=``, and on the TPU's op line its instruction
takes that name (``%lean_decode_fused.8 = ... custom-call(...)``). This
reader keeps the ops named by the decode kernels, so a Pallas call of
another kind (the chunked prefill's) in the window is not counted as decode
attention. A program whose kernels carry no such name (``%closed_call.12``)
gives nothing to read."""
import copy

from bench.harness import tracing
from bench.harness.spec import metric_reader

KERNELS = ("lean_decode_fused", "lean_decode_partials", "lean_merge",
           "lean_cascade_fused")


def kernel_name(op: str) -> str:
    """The instruction's name without its ``%`` and ``.<n>`` suffix."""
    return op.partition(" = ")[0].lstrip("%").rsplit(".", 1)[0]


def read(record):
    if record.trace is None:
        return None
    ops = [e for e in record.trace.ops if kernel_name(e.name) in KERNELS]
    if not ops:
        return None
    named = copy.copy(record)
    named.trace = tracing.Trace(ops=ops, modules=record.trace.modules,
                                spans=record.trace.spans)
    return metric_reader("decode_attn_roofline")(named)
