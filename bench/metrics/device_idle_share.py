"""device_idle_share: the share of the traced window in which no operation
ran on the device, in %: 1 - (union of device op intervals / window)."""
from bench.harness import tracing


def read(record):
    if record.trace is None or record.trace_window is None:
        return None
    lo, hi = record.trace_window
    busy = tracing.busy_seconds(record.trace.ops, lo, hi)
    return 100.0 * (1.0 - busy / ((hi - lo) * 1e-9))
