"""On-chip benchmark of the serving path: ``python bench/run.py --workload ...``.

``BENCHMARK.json`` at the root of the repo names the cells; everything that
belongs to one configuration, traffic mix, cell or per-layer metric lives in
a file of its own under this directory and is found by that name.
"""
