"""Mean host time of the paged engine's decode dispatches: from the
previous dispatch's end (or the end of a wait) to this step's result on
the host, so the engine's per-step host work is in it."""


def read(run):
    d = run.work.get("decode_s")
    return 1e3 * sum(d) / len(d) if d else None
