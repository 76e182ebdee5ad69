"""The yardstick: traffic generation, metric arithmetic, trace reduction.

Nothing here is imported by the program under test, and only the two
`*_child.py` modules import the program (they run in the one process that
holds the chip). The parent (`benchmark/run.py`) never initialises a JAX
backend.
"""
