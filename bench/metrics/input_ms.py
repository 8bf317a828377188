"""Host time per window step to build the step's input (the trainer's
TokenPipeline construction and next_batch), from the harness's span."""


def read(ctx):
    if not ctx.input_s:
        return None
    return 1e3 * sum(ctx.input_s) / len(ctx.input_s)
