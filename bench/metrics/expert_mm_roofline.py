"""The held experts' grouped matmuls against the chip's bf16 peak: their
FLOPs a step (``scopes.expert_mm_flops``: forward and backward, no
recompute, as ``mfu`` counts) over the device time a step under the scope
``moe_experts`` (the three matmuls and the SwiGLU product between them, in
the forward, the recompute and the backward)."""

from bench import scopes


def read(ctx):
    run = scopes.of_run(ctx)
    if not run or not ctx.steps or not run.get("moe_experts"):
        return None
    flops = scopes.expert_mm_flops(ctx.cell.run, ctx.cell.tokens_per_step)
    least_s = flops / ctx.peak["bf16_flops_per_s"]
    return 100.0 * least_s / (run["moe_experts"] / ctx.steps)
