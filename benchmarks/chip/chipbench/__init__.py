"""Shared machinery of the on-chip benchmark (``benchmarks/chip/run.py``).

Everything that belongs to one configuration, traffic mix, per-layer
metric, work count or reference lives in a file of its own beside this
package and is found by name (``spec.py``); this package holds only what
every cell shares.
"""
