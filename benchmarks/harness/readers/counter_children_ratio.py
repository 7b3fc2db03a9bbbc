"""``program_counter``: one statistic over the children of a counter of the
program, over another's (or the same counter's): what each child rose by in
the traced slice (the configuration lists the counter under
``slice_counters``). A child is one combination of label values; ``label``
keeps the children whose LAST label has that value, and ``stat`` is ``sum``,
``max`` or ``mean`` over those kept. So ``sum`` of the ``zero`` children over
``sum`` of all is a share, and ``max`` over ``mean`` says how uneven a load
is. A program without the counter, or a slice in which it did not rise,
gives nothing, never 0."""

_STATS = {"sum": sum, "max": max, "mean": lambda v: sum(v) / len(v)}


def _stat(counters: dict, counter: str, stat: str = "sum", label=None):
    rises = [v for k, v in counters.get(counter, {}).items()
             if label is None or k.split(",")[-1] == label]
    return _STATS[stat](rises) if rises else None


def read(record: dict, numerator: dict, denominator: dict,
         scale: float = 1.0) -> float | None:
    counters = (record.get("slice") or {}).get("counters") or {}
    num = _stat(counters, **numerator)
    den = _stat(counters, **denominator)
    if num is None or not den or den <= 0:
        return None
    return scale * num / den
