"""Device time of the sliding-window layers' whole mixers (every operation whose scope path
holds ``ds_attn_window``: projections, per-head norms, the rotary table, the flash kernel's
banded calls, forward, recomputed forward and backward) over the traced window. None without
a trace, a catalog or such a scope."""

from benchmarks import swa_spans


def read(record):
    result = swa_spans.analyse(record)
    if result is None or swa_spans.WINDOW not in result["scope_s"]:
        return None
    return 100.0 * result["scope_s"][swa_spans.WINDOW] / result["window_s"]
