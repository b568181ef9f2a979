"""Seconds from the process's start to the window's: making the weights,
compiling or loading every program the cell uses, and warming up."""


def read(run):
    return run.setup_s
