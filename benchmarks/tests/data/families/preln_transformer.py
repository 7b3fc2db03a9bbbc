"""The benchmark's own family (``benchmarks/families/preln_transformer.py``),
for the tiny encoder and decoder here: a data directory carries the families
its configurations name, and this one is not to be kept twice."""

from benchmarks.families.preln_transformer import *  # noqa: F401,F403
