"""Shared code of the benchmark: specs, device and peaks, weights, traffic,
FLOP and byte counts, the served window, trace reduction and the check."""
