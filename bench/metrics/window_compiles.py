"""window_compiles: programs that JAX built or loaded from its persistent
cache inside the measured window (its own backend-compile events). Set-up
warms every program the window uses, so anything counted here stalled the
served tokens. Layer: jitted step dispatch (``serving/engine.py``)."""


def read(record):
    return float(record.window_compiles)
