"""Seconds XLA's backend spent compiling during set-up (``jax.monitoring``
``backend_compile_duration`` events up to the window's start). Near zero once every
program is in the persistent cache."""


def read(record):
    return record["setup"].get("compile_s")
