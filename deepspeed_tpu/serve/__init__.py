"""TPU-native serving engine: block-paged KV cache + continuous batching.

The reference DeepSpeed 0.3.0 ships no inference engine; this package is the
serving layer (ROADMAP.md's first aim names its metrics; A18 is what keeps it
out of the benchmark). Three parts:

- :mod:`block_allocator` — host-side free-list allocator over a fixed HBM pool
  of KV pages, with per-sequence block tables and refcounted copy-on-write
  forks for beam search (vLLM's PagedAttention memory model, SOSP '23);
- :mod:`paged` + :mod:`scheduler` — fixed-shape paged decode/prefill programs
  (one compile each, ever) and an iteration-granular continuous-batching
  scheduler with chunked prefill interleaved into in-flight decodes (Orca,
  OSDI '22);
- :mod:`engine` — the ``deepspeed_tpu.init_inference``-shaped facade wrapping
  models/gpt2.py, config block ``"serving"``, telemetry Serving/* scalars;
- :mod:`request_trace` — the serving observatory: per-request lifecycle
  ledger, latency percentiles, preemption-waste accounting, SLO
  classification, ``ds-tpu serve-timeline`` Perfetto export (config block
  ``"serving": {"request_trace": ...}``).

``serve/oracle.py`` holds the dense-cache mirror programs the equivalence
tests and ``ds-tpu serve-sim`` bit-compare the paged path against.
"""

# Lazy exports (PEP 562): `ds-tpu serve-timeline` dispatches into
# serve/request_trace.py on machines with no accelerator runtime (post-mortem
# boxes), so importing this package must not pull in the engine's jax stack.
_EXPORTS = {
    "AllocationError": ".block_allocator",
    "BlockAllocator": ".block_allocator",
    "FleetRouter": ".router",
    "InferenceEngine": ".engine",
    "Request": ".scheduler",
    "RequestOutput": ".scheduler",
    "RequestTracer": ".request_trace",
    "Scheduler": ".scheduler",
    "StreamingHistogram": ".request_trace",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    if name in _EXPORTS:
        from importlib import import_module
        val = getattr(import_module(_EXPORTS[name], __name__), name)
        globals()[name] = val
        return val
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
