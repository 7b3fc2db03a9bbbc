"""The benchmark's own yardstick: traffic, peaks, the trace reduction, what
the plain references share and the comparison that decides ``correct``.
What depends on a model's architecture (its sizes, weight tree, reference,
work counts, cache's bytes) is in ``../families/``, one file a family.
Nothing here is imported by the program; the two drivers and the families
import it."""
