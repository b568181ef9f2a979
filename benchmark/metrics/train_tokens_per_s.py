"""Tokens of every step completed in the window over the window's wall
time."""


def read(run):
    if "train_tokens" not in run.work:
        return None
    return run.work["train_tokens"] / run.window_s
