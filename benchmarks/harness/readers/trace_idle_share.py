"""``device_trace``: the share of the traced slice in which no operation ran
on the device, 1 - (union of device-op intervals) / slice, in percent."""


def read(record: dict) -> float | None:
    tr = record.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
