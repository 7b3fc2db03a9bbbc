"""``program_span``: the mean of a registry histogram of the program over
the window, sum's change over count's change, in milliseconds (the host
clock round one span inside the program)."""


def read(record: dict, metric: str) -> float | None:
    dsum, dcount = record.get("hist", {}).get(metric, (0.0, 0))
    if dcount <= 0:
        return None
    return 1e3 * dsum / dcount
