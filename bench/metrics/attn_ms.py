"""Device time per window step in the attention cores (``models/attention.py``:
dense, chunked, banded), the ops under the scope ``attn_core`` in the
forward, the recompute and the backward (``bench/scopes.py``); the
projections around them are not counted."""

from bench import scopes


def read(ctx):
    run = scopes.of_run(ctx)
    if not run or not ctx.steps or not run.get("attn_core"):
        return None
    return 1e3 * run["attn_core"] / ctx.steps
