"""``device_trace``: a kernel's share of its roofline, in percent.

The least time the chip could take for one call - the larger of FLOPs over
peak FLOP/s and bytes over peak bytes/s, both counted by the family of the
cell being run (what the algorithm needs, from shapes) - over the mean
device time of the kernel's events in the traced slice. ``kernels`` lists
the kernel names taken together (their times per call add up); ``work``
names the function of the family's file (``record["family"]``), so a kernel
that two families share is counted by each for its own shapes. A trace
without the kernel gives nothing, never 0."""

from ..peaks import chip_peaks


def _least_seconds(record: dict, work: str):
    """(seconds at peak FLOP/s, seconds at peak bytes/s) of one call."""
    flops, nbytes = getattr(record["family"], work)(record["slice"])
    pk = chip_peaks(record["device_kind"])
    return (flops / pk["bf16_flops_per_s"],
            (nbytes or 0.0) / pk["hbm_bytes_per_s"])


def read(record: dict, kernels: list, work: str) -> float | None:
    tr, sl = record.get("trace"), record.get("slice")
    if not tr or not sl:
        return None
    per_call = 0.0
    for k in kernels:
        seconds, count = tr["kernels"].get(k, (0.0, 0))
        if count == 0:
            return None
        per_call += seconds / count
    least = max(_least_seconds(record, work))
    if least <= 0:
        return None
    return 100.0 * least / per_call


def bound(record: dict, work: str) -> str:
    """Which of the two bounds the roofline ("flops" or "bytes")."""
    by_flops, by_bytes = _least_seconds(record, work)
    return "flops" if by_flops >= by_bytes else "bytes"
