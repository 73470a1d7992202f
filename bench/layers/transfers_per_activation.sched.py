"""Host<->device transfers per device-scored activation: the scoring
backend's ``uploads`` and ``readbacks`` counters over its ``device``
counter, in the window."""


def read(record):
    c = record["counters"]
    n = c.get("device_scored", 0)
    if record.get("program") is None or "uploads" not in c or not n:
        return None
    return (c["uploads"] + c["readbacks"]) / n
