"""Device busy time of the traced window per surrogate configuration
completed in it (in the part of it the trace covers: see ``bench/trace.py``)."""


def read(record):
    trace = record.get("trace")
    c = record["counters"]
    if trace is None or not c.get("calls"):
        return None
    calls = trace["covered"].get("run_batch", 0) if trace["cut"] else c["calls"]
    if not calls:
        return None
    return 1e3 * trace["busy_s"] / (calls * c["configs"] / c["calls"])
