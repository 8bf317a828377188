"""The attention core against the chip's bf16 peak: ``bench/flops.py``'s
attention term a step (the scores and values of every layer, forward and
backward, no recompute: ``train_step_flops`` with no parameters) over the
device time a step under the scope ``attn_core``, in the forward, the
recompute and the backward.  It counts the same work whatever core runs, so
it says how far any core is from the hardware; recompute is in the time,
not the FLOPs, so a core under the block remat tops out near 75%.

The time is read as ``attn_ms`` reads it (``bench/scopes.py``), but from the
compiled step's HLO with each instruction on one line.  A Pallas kernel
that carries metadata of its own (the splash kernel's block sizes) prints
its ``op_name`` on a continuation line; read a line at a time, the kernel
takes the scope of its first user that has one, and in the backward that
is the rotary embedding fused into the gradient's transpose, outside the
scope.  Joined, each kernel call is read by its own ``op_name``.
"""

from bench import flops, phases, scopes, trace


def joined(text: str) -> str:
    """HLO text with each instruction's continuation lines appended to its
    first: a line that opens no instruction or computation, is not a lone
    closing brace and follows an instruction continues it."""
    out = []
    for line in text.splitlines():
        if (out and phases._INSTR.match(out[-1]) and line.strip()
                and line.strip() != "}" and not phases._INSTR.match(line)
                and not phases._COMPUTATION.match(line)):
            out[-1] += line
        else:
            out.append(line)
    return "\n".join(out)


def attn_flops(cell) -> float:
    """The attention term of ``bench/flops.py`` for a step of the cell."""
    return flops.train_step_flops(cell.run, 0.0, cell.rows, cell.wl["seq"])


def read(ctx):
    s = _attn_core_s(ctx)
    if not s or not ctx.steps:
        return None
    least_s = attn_flops(ctx.cell) / ctx.peak["bf16_flops_per_s"]
    return 100.0 * least_s / (s / ctx.steps)


def _attn_core_s(ctx):
    """Device seconds in the traced window under ``attn_core``, read once
    and kept on ``ctx``; None where there is no trace to read."""
    if not hasattr(ctx, "attn_core_s"):
        log_dir = phases._trace_dir()
        ctx.attn_core_s = _read(ctx, log_dir) if log_dir else None
    return ctx.attn_core_s


def _read(ctx, log_dir: str):
    from bench import harness

    record = phases.extract(log_dir)
    t0, t1 = trace.window_of(record)
    ops = [op for op in record["device_ops"]
           if op[1] < t1 and op[1] + op[2] > t0
           and op[0].split(".")[0] not in trace.CONTAINERS]
    if not ops:
        return None
    hlo = scopes.hlo_scopes(joined(phases.step_hlo(ctx.cell)))
    if any(n not in hlo or hlo[n][1] != record["calls"][n] for n, *_ in ops):
        harness.info("attn_roofline: the window's device ops differ from the "
                     "rebuilt step; not read")
        return None
    n_dev = max(len({op[3] for op in ops}), 1)
    s = sum((min(start + dur, t1) - max(start, t0)) / 1e9 / n_dev
            for name, start, dur, _ in ops if hlo[name][0] == "attn_core")
    harness.info(f"attn_roofline: attn_core {1e3 * s / max(ctx.steps, 1):.3f} "
                 "ms a step, each instruction read whole")
    return s or None
