"""Which of the program's named scopes an op ran under.

A ``jax.named_scope`` becomes a component of the ``op_name`` metadata of
every op traced inside it: ``jit(pool_step)/layers/while/body/mlp/
dot_general``. Where the scope is differentiated the component is wrapped
by the transformation, ``jvp(lm_head)`` forward and
``transpose(jvp(lm_head))`` backward, and it counts as the scope all the
same. Scopes nest, and the innermost one claims the op. The profiler's
trace of a TPU carries each op's ``op_name`` (its ``tf_op`` statistic),
so this needs no other record of the compiled program.
"""
from __future__ import annotations

import re

try:
    from repro.runtime.trace_names import SCOPES
except ImportError:                 # a program without named scopes
    SCOPES = ()

_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")


def scope_of(path: str, scopes=SCOPES):
    """The innermost of ``scopes`` among the components of an ``op_name``
    path, each unwrapped from the transformations around it; None where
    there is none. A fused op may carry several paths joined by ``;``:
    the first, its root's, is read."""
    found = None
    for comp in (path or "").split(";")[0].split("/"):
        m = _WRAPPED.match(comp)
        while m:
            comp = m.group(1)
            m = _WRAPPED.match(comp)
        if comp in scopes:
            found = comp
    return found
