"""Per-layer metric readers, one file per metric, found by its name.

``bench/metrics/<name>.py`` defines ``read(ctx)``, which returns the
metric's value or None where the traced run holds nothing to read.  ctx
carries the reduced trace (``ctx.trace``, see ``bench/trace.py``), the
window (``ctx.steps``, ``ctx.window_s``), the host spans the harness timed
(``ctx.input_s``), the work counts (``ctx.flops_per_step``,
``ctx.update_bytes_per_step``) and the device's peaks (``ctx.peak``).
"""
