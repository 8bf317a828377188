"""Device time per window step in the update phases of the train step:
clip, optimizer, apply, isp_split, sent_count and exchange (``optim/``,
``core/isp``, ``dist/compression``), from the program's phase scopes
joined to the trace's device ops (``bench/phases.py``)."""

from bench import phases


def read(ctx):
    run = phases.of_run(ctx)
    if run is None or not ctx.steps:
        return None
    update_s = sum(run["device_phases"].get(p, 0.0) for p in phases.UPDATE)
    return 1e3 * update_s / ctx.steps if update_s > 0 else None
