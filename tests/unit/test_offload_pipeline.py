"""Pipelined ZeRO-Offload step tests (CPU-only, no accelerator needed).

The pipeline claim is a WALL-CLOCK claim, so it is proven here with an
injectable transfer executor that adds simulated per-item latency: the serial
executor's step must cost ~ Σfetch + Σadam + Σpush while the pipelined
executor's step must cost <= 1.15 x max(Σfetch, Σadam, Σpush) — and both must
produce bit-identical optimizer state (Adam is elementwise, so chunking and
overlap may not change a single bit)."""

import threading
import time

import numpy as np
import pytest

import jax

from deepspeed_tpu.ops.cpu_adam import (DeepSpeedCPUAdam, PipelinedTransferExecutor,
                                        SerialTransferExecutor)
from deepspeed_tpu.runtime.zero.sharding import chunk_spans


def _params(rng, n_leaves=8, size=2000):
    return {f"p{i}": rng.normal(size=(size,)).astype(np.float32)
            for i in range(n_leaves)}


def _grads(rng, params):
    return {k: rng.normal(size=v.shape).astype(np.float32) for k, v in params.items()}


class _LatencyMixin:
    """Adds per-item sleep to each lane and records lane busy seconds plus the
    maximum number of simultaneously-running lane tasks (the caller thread's
    Adam is not counted, so max_concurrency >= 2 means the fetch and push lanes
    really ran at the same time)."""

    def _init_latency(self, fetch_delay, push_delay):
        self.fetch_delay, self.push_delay = fetch_delay, push_delay
        self._lock = threading.Lock()
        self._active = 0
        self.max_concurrency = 0
        self.lane_busy = {"fetch": 0.0, "push": 0.0}

    def _wrap(self, fn, delay, lane):
        def run(*args):
            with self._lock:
                self._active += 1
                self.max_concurrency = max(self.max_concurrency, self._active)
            t0 = time.perf_counter()
            try:
                time.sleep(delay)
                return fn(*args)
            finally:
                with self._lock:
                    self._active -= 1
                    self.lane_busy[lane] += time.perf_counter() - t0
        return run


class LatencySerialExecutor(_LatencyMixin, SerialTransferExecutor):
    def __init__(self, fetch_delay, push_delay):
        self._init_latency(fetch_delay, push_delay)

    def submit_fetch(self, fn, *args):
        return super().submit_fetch(self._wrap(fn, self.fetch_delay, "fetch"), *args)

    def submit_push(self, fn, *args):
        return super().submit_push(self._wrap(fn, self.push_delay, "push"), *args)


class LatencyPipelinedExecutor(_LatencyMixin, PipelinedTransferExecutor):
    def __init__(self, fetch_delay, push_delay):
        super().__init__()
        self._init_latency(fetch_delay, push_delay)

    def submit_fetch(self, fn, *args):
        return super().submit_fetch(self._wrap(fn, self.fetch_delay, "fetch"), *args)

    def submit_push(self, fn, *args):
        return super().submit_push(self._wrap(fn, self.push_delay, "push"), *args)


def _run_steps(opt, grads_seq, **hyper):
    for step, g in enumerate(grads_seq, start=1):
        opt.step_regions(opt.begin_grad_fetch(g), step=step, **hyper)


def test_pipelined_step_bit_equal_to_serial():
    """Overlap and chunking may not change the update by a single bit: Adam is
    elementwise, so a chunked kernel call sequence must equal the one-shot call."""
    rng = np.random.default_rng(0)
    params = _params(rng, n_leaves=6, size=3001)  # odd size: chunks don't divide evenly
    grads_seq = [_grads(rng, params) for _ in range(3)]
    hyper = dict(lr=1e-2, weight_decay=0.01, grad_scale=0.5)

    serial = DeepSpeedCPUAdam(params, pipeline=False)
    serial.transfer_executor = SerialTransferExecutor()
    piped = DeepSpeedCPUAdam(params, pipeline=True, pipeline_depth=3,
                             max_region_elements=512)  # forces ~6 chunks per leaf
    try:
        _run_steps(serial, grads_seq, **hyper)
        _run_steps(piped, grads_seq, **hyper)
        np.testing.assert_array_equal(piped.fp32, serial.fp32)
        np.testing.assert_array_equal(piped.exp_avg, serial.exp_avg)
        np.testing.assert_array_equal(piped.exp_avg_sq, serial.exp_avg_sq)
    finally:
        piped.close()


def test_pipelined_wall_clock_overlaps_simulated_latency():
    """With F=40ms fetch / P=10ms push injected per region, the serial step costs
    about the SUM of the lanes while the pipelined step costs about the MAX —
    the ISSUE's total ~ max(Σfetch, Σadam, Σpush) acceptance bound."""
    F, P, N = 0.040, 0.010, 8
    rng = np.random.default_rng(1)
    params = _params(rng, n_leaves=N, size=1500)
    g = _grads(rng, params)
    hyper = dict(lr=1e-3, weight_decay=0.0)

    serial = DeepSpeedCPUAdam(params)
    serial.transfer_executor = LatencySerialExecutor(F, P)
    t0 = time.perf_counter()
    serial.step_regions(serial.begin_grad_fetch(g), step=1, **hyper)
    serial_wall = time.perf_counter() - t0
    s_fetch = serial.transfer_executor.lane_busy["fetch"]
    s_push = serial.transfer_executor.lane_busy["push"]
    s_adam = serial.last_step_timing["host_adam"]
    # serial ~ sum of the three lanes (sleep scheduling noise only adds time,
    # so the lower bound is the meaningful one)
    assert serial_wall >= 0.85 * (s_fetch + s_adam + s_push), \
        (serial_wall, s_fetch, s_adam, s_push)

    piped = DeepSpeedCPUAdam(params, pipeline_depth=2)
    ex = piped.transfer_executor = LatencyPipelinedExecutor(F, P)
    try:
        t0 = time.perf_counter()
        piped.step_regions(piped.begin_grad_fetch(g), step=1, **hyper)
        piped_wall = time.perf_counter() - t0
    finally:
        piped.close()
        ex.shutdown()
    p_fetch = ex.lane_busy["fetch"]
    p_push = ex.lane_busy["push"]
    p_adam = piped.last_step_timing["host_adam"]
    bound = 1.15 * max(p_fetch, p_adam, p_push)
    assert piped_wall <= bound, (piped_wall, bound, p_fetch, p_adam, p_push)
    # the lanes really overlapped: >= 2 executor tasks in flight at once, and the
    # pipelined wall beat the serial wall outright
    assert ex.max_concurrency >= 2, ex.max_concurrency
    assert piped_wall < serial_wall, (piped_wall, serial_wall)
    # identical state out of both walks
    np.testing.assert_array_equal(piped.fp32, serial.fp32)


def test_pipelined_timing_schema_and_overlap_fields():
    """step_regions must publish the lane-busy/overlap schema docs/zero-offload.md describes."""
    rng = np.random.default_rng(2)
    params = _params(rng, n_leaves=4, size=900)
    opt = DeepSpeedCPUAdam(params, max_region_elements=256)
    try:
        opt.step_regions(opt.begin_grad_fetch(_grads(rng, params)), step=1, lr=1e-3)
        t = opt.last_step_timing
    finally:
        opt.close()
    for key in ("fetch_wait", "host_adam", "push", "total", "fetch_busy",
                "push_busy", "pipeline_depth", "region_cap", "n_work_items",
                "regions"):
        assert key in t, key
    assert t["pipeline_depth"] == 2 and t["region_cap"] == 256
    assert t["n_work_items"] == sum(-(-r.size // 256) for r in opt._regions)
    assert len(t["regions"]) == len(opt._regions)
    for r in t["regions"]:
        assert r["chunks"] >= 1 and r["size"] > 0
        assert r["fetch"] >= 0 and r["adam"] >= 0 and r["push"] >= 0


def test_region_cap_splits_and_covers():
    """An explicit max_region_elements must cap every work item's covered range
    and the ranges must exactly tile each region."""
    cap = 1024
    rng = np.random.default_rng(3)
    params = {"big": rng.normal(size=(5000,)).astype(np.float32),
              "small": rng.normal(size=(100,)).astype(np.float32)}
    opt = DeepSpeedCPUAdam(params, max_region_elements=cap)
    try:
        handles = opt.begin_grad_fetch(_grads(rng, params))
        covered = {}
        for kind, _, r, rel_lo, rel_hi, win in handles:
            assert rel_hi - rel_lo <= cap
            assert win <= rel_lo and rel_hi <= win + cap  # window carries the range
            covered.setdefault(id(r), []).append((rel_lo, rel_hi, r.size))
        assert len(covered) == 2
        for spans in covered.values():
            spans.sort()
            assert spans[0][0] == 0 and spans[-1][1] == spans[0][2]
            for (a_lo, a_hi, _), (b_lo, b_hi, _) in zip(spans, spans[1:]):
                assert b_lo == a_hi  # contiguous, non-overlapping coverage
        big_items = [h for h in handles if h[2].size == 5000]
        assert len(big_items) == -(-5000 // cap)
    finally:
        opt.close()


def test_chunk_spans_windowing():
    """chunk_spans: fixed-width windows (one compiled slice per cap), the last
    window right-aligned so every [lo, hi) stays inside its window."""
    assert chunk_spans(10, None) == [(0, 10, 0)]
    assert chunk_spans(10, 0) == [(0, 10, 0)]
    assert chunk_spans(10, 16) == [(0, 10, 0)]
    spans = chunk_spans(10, 4)
    assert spans == [(0, 4, 0), (4, 8, 4), (8, 10, 6)]
    for lo, hi, win in spans:
        assert win <= lo and hi <= win + 4
    assert chunk_spans(8, 4) == [(0, 4, 0), (4, 8, 4)]


def test_autotune_sets_cap_once_and_respects_pin():
    rng = np.random.default_rng(4)
    params = _params(rng, n_leaves=3, size=4000)
    auto = DeepSpeedCPUAdam(params)  # max_region_elements="auto"
    try:
        assert not auto._autotuned
        auto.step_regions(auto.begin_grad_fetch(_grads(rng, params)), step=1, lr=1e-3)
        assert auto._autotuned
        assert (1 << 20) <= auto._auto_cap <= (64 << 20)
        cap_after_first = auto._auto_cap
        auto.step_regions(auto.begin_grad_fetch(_grads(rng, params)), step=2, lr=1e-3)
        assert auto._auto_cap == cap_after_first  # tunes once, not every step
    finally:
        auto.close()

    pinned = DeepSpeedCPUAdam(params, max_region_elements=512)
    try:
        pinned.step_regions(pinned.begin_grad_fetch(_grads(rng, params)), step=1,
                            lr=1e-3)
        assert not pinned._autotuned and pinned.region_cap() == 512
    finally:
        pinned.close()

    with pytest.raises(ValueError, match="max_region_elements"):
        DeepSpeedCPUAdam(params, max_region_elements=-5)


def test_serial_executor_disables_chunking():
    """pipeline=False must reproduce the legacy one-item-per-region walk."""
    rng = np.random.default_rng(5)
    params = _params(rng, n_leaves=3, size=3000)
    opt = DeepSpeedCPUAdam(params, pipeline=False, max_region_elements=512)
    assert opt.region_cap() is None  # cap only applies to the pipelined walk
    handles = opt.begin_grad_fetch(_grads(rng, params))
    assert len(handles) == len(opt._regions)
    opt.step_regions(handles, step=1, lr=1e-3)
    assert opt.last_step_timing["pipeline_depth"] == 1
