"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""


def read(run):
    return None if run.trace is None else run.trace.idle_share()
