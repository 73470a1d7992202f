"""Share of the window in DADA's own host work: the self time of the
``dada.*`` spans (``repro.core.obs``), which leaves out the scoring
programs' spans nested in them."""


def read(record):
    program = record.get("program")
    if program is None:
        return None
    own = [v["self_s"] for k, v in program.items() if k.startswith("dada.")]
    if not own:
        return None
    return 100.0 * sum(own) / record["window_s"]
