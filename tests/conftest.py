"""Test bootstrap: one hypothesis profile for the whole suite.

Interpret-mode Pallas examples compile on first use, so a per-example
deadline measures the compiler, not the property; and the example database
would write ``.hypothesis/`` into the checkout. Both are off.
"""
from hypothesis import settings

settings.register_profile("repro", deadline=None, database=None)
settings.load_profile("repro")
