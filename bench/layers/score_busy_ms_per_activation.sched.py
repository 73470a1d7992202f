"""Device busy time of the traced window per activation scored on the
device: what one device-scored decision costs the chip."""


def read(record):
    trace = record.get("trace")
    n = record["counters"].get("device_scored", 0)
    if trace is None or not n or trace["cut"]:
        return None
    return 1e3 * trace["busy_s"] / n
