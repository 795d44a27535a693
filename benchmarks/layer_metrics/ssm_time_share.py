"""Device time of the operations under the Mamba-2 mixers' ``ds_ssm`` scope (projections,
convolution, the scan, the gated norm; forward, recomputed forward and backward) over the
traced window."""

from benchmarks import ssm_spans


def read(record):
    result = ssm_spans.analyse(record)
    if result is None:
        return None
    return 100.0 * result["scope_s"].get(ssm_spans.SSM, 0.0) / result["window_s"]
