"""Host time around the scoring programs per device-scored activation: the
self time of the ``score`` and ``search`` programs' pack, upload and
dispatch spans (``repro.core.obs``), in ms."""

SPANS = [f"{prog}.{phase}" for prog in ("score", "search")
         for phase in ("pack", "upload", "dispatch")]


def read(record):
    program = record.get("program")
    n = record["counters"].get("device_scored", 0)
    if program is None or not n:
        return None
    return 1e3 * sum(program.get(s, {}).get("self_s", 0.0) for s in SPANS) / n
