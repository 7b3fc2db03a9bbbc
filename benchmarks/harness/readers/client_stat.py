"""``host_clock``: a statistic the benchmark's own clients took over the
window (the tail of the time to the first token, say), read as a per-layer
metric where it swings too widely to be held to a bound."""


def read(record: dict, key: str) -> float | None:
    return record.get("client", {}).get(key)
