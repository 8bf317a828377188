"""The update phase's share of its roofline: the bytes it must move a step
(``bench/work/<mode>.py``) at the chip's HBM bandwidth, over its device
time a step (``update_ms``)."""

from bench import harness

_update_ms = harness.load_module(harness.BENCH / "metrics" / "update_ms.py")


def read(ctx):
    ms = _update_ms.read(ctx)
    if ms is None:
        return None
    least_s = ctx.update_bytes_per_step / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
