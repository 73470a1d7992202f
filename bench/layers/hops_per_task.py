"""Demand-copy hops per task placed in the window: the engine's counters of
host-link, peer-fabric and host-staged hops (``hops_host``, ``hops_peer``,
``hops_staged``) over ``tasks_placed``."""

ROUTES = ("hops_host", "hops_peer", "hops_staged")


def read(record):
    c = record["counters"]
    if not c.get("tasks_placed") or any(k not in c for k in ROUTES):
        return None
    return sum(c[k] for k in ROUTES) / c["tasks_placed"]
