"""Share of the traced window in which no operation ran on the device
(averaged over the cell's chips), in percent."""


def read(record):
    trace = record.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
