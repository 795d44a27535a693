"""Device time of the operations whose scope path holds both ``ds_loop`` and
``rematted_computation`` (the blocks' second forward, made in the backward by the engine's
``checkpoint_wrapper``) over the traced window. An operation the compiler gave no scope path
is not counted: reads low."""

from benchmarks import loop_spans


def read(record):
    result = loop_spans.analyse(record)
    if result is None:
        return None
    return 100.0 * loop_spans.loop_seconds(result, "recomputed") / result["window_s"]
