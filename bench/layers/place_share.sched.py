"""Share of the window spent inside ``strategy.place`` (the policies and
their scoring), by the host clock around each activation; the rest is the
engine's event loop."""


def read(record):
    place = record["spans"].get("place")
    if place is None:
        return None
    return 100.0 * place / record["window_s"]
