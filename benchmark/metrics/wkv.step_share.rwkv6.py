"""Percent of the train step's device time in the traced window under the
``wkv`` scope: the rwkv6 recurrence, forward, recomputed and backward."""
from stats import share


def read(run):
    by_scope = run.work.get("train_step_scope_s") or {}
    if by_scope.get("wkv") is None:
        return None
    return share(by_scope["wkv"], sum(by_scope.values()))
