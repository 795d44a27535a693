"""Device time of the operations of the gradient program that JAX names as recomputed
(``rematted_computation`` in the scope path: what ``jax.checkpoint`` makes again in the
backward, the engine's whole blocks and the small ones inside a mixer) over the traced
window. An operation the compiler gave no scope path is not counted: reads low."""

from benchmarks import ssm_spans


def read(record):
    result = ssm_spans.analyse(record)
    if result is None:
        return None
    return 100.0 * result["scope_s"].get(ssm_spans.RECOMPUTED, 0.0) / result["window_s"]
