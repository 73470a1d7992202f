"""Host time of the surrogate per configuration: the self time of
``batch.plan`` and of the episode program's pack, upload and dispatch spans
(``repro.core.obs``), summed over the worker threads, in ms."""

SPANS = ("batch.plan", "episode.pack", "episode.upload", "episode.dispatch")


def read(record):
    program = record.get("program")
    n = record["counters"].get("configs", 0)
    if program is None or "batch.run" not in program or not n:
        return None
    return 1e3 * sum(program.get(s, {}).get("self_s", 0.0) for s in SPANS) / n
