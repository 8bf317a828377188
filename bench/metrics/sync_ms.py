"""Host time per window step inside the trainer's ``train.sync`` span: the
``float(loss)`` / ``float(sent)`` that waits for the step's results."""

from bench import phases


def read(ctx):
    run = phases.of_run(ctx)
    if run is None or not ctx.steps or not run["loop_spans"].get("train.sync"):
        return None
    return 1e3 * run["loop_spans"]["train.sync"] / ctx.steps
