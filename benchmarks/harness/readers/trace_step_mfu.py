"""``device_trace``: the whole step's share of the chips' peak, in percent:
the model FLOPs of the work done in the traced slice, as the family of the
cell being run counts them (``work`` names the function of the family's
file, ``record["family"]``), over (the
trace's device window, first operation's start to last operation's end, x
chips x peak bf16 FLOP/s). A train slice is fenced at both ends and holds
exactly its steps; a serve slice holds the tokens that reached a client in
an interval of that length (``serve_driver.metrics``). Recomputation does not
count."""

from ..peaks import chip_peaks


def read(record: dict, work: str) -> float | None:
    tr, sl = record.get("trace"), record.get("slice")
    if not tr or not sl or tr["window_s"] <= 0:
        return None
    flops, _ = getattr(record["family"], work)(sl)
    if flops <= 0:
        return None
    peak = chip_peaks(record["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (tr["window_s"] * record["chips"] * peak)
