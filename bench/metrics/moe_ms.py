"""Device time per window step in the expert layers (``models/moe.py``):
the ops under the scopes ``moe_route``, ``moe_dispatch``, ``moe_experts``
and ``moe_combine``, in the forward, the recompute and the backward
(``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    run = scopes.of_run(ctx)
    if not run or not ctx.steps:
        return None
    s = sum(run.get(k, 0.0) for k in scopes.MOE)
    return 1e3 * s / ctx.steps if s > 0 else None
