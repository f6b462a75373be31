"""Named scopes of the smoother's stages.

Each stage is traced inside ``jax.named_scope(<name>)``, so every HLO
instruction it lowers to carries the name in its ``op_name`` metadata and
a profiler trace can charge device time to the stage (`bench/stages.py`
reduces a trace that way; DESIGN.md §6 lists the names). Scopes change
metadata only, never the computation. Nested stages keep the innermost
name: the linearization inside the cost reads ``smoother.linearize``.
"""
from __future__ import annotations

import functools

import jax

ITERATE = "smoother.iterate"      # the whole iterated loop
LINEARIZE = "smoother.linearize"  # every linearize_model_* call
FILTER = "smoother.filter"        # filtering pass, element preparation too
SMOOTH = "smoother.smooth"        # smoothing pass, element preparation too
COMBINE = "smoother.combine"      # the scans' combine, any lowering
COST = "smoother.cost"            # `cost.smoothing_cost`
LOGLIK = "smoother.loglik"        # `iterated.smoothed_log_likelihood`

STAGES = (ITERATE, LINEARIZE, FILTER, SMOOTH, COMBINE, COST, LOGLIK)


def scoped(name: str):
    """Decorator: trace the function inside ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def traced(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return traced
    return wrap
