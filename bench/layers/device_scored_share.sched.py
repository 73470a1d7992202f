"""Share of the window's activations that the scoring backend scored on the
device (``JaxScoringBackend.counts["device"]`` over all activations)."""


def read(record):
    c = record["counters"]
    if not c.get("activations"):
        return None
    return 100.0 * c["device_scored"] / c["activations"]
