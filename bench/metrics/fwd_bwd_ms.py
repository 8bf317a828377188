"""Device time per window step in the train step's ``fwd_bwd`` phase: the
model's forward and backward (``models/``), from the program's phase
scopes joined to the trace's device ops (``bench/phases.py``)."""

from bench import phases


def read(ctx):
    run = phases.of_run(ctx)
    if run is None or not ctx.steps or not run["device_phases"].get("fwd_bwd"):
        return None
    return 1e3 * run["device_phases"]["fwd_bwd"] / ctx.steps
