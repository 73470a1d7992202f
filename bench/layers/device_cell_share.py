"""Share of the window's task x resource score cells that the scoring
backend computed on the device (counters ``cells_device`` over
``cells_device + cells_host``)."""


def read(record):
    c = record["counters"]
    n = c.get("cells_device", 0) + c.get("cells_host", 0)
    if "cells_device" not in c or not n:
        return None
    return 100.0 * c["cells_device"] / n
