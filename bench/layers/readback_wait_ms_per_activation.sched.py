"""Time spent reading the scoring programs' results back, which waits for
the device, per device-scored activation: the ``score.readback`` and
``search.readback`` spans (``repro.core.obs``), in ms."""

SPANS = ("score.readback", "search.readback")


def read(record):
    program = record.get("program")
    n = record["counters"].get("device_scored", 0)
    if program is None or not n:
        return None
    return 1e3 * sum(program.get(s, {}).get("total_s", 0.0) for s in SPANS) / n
