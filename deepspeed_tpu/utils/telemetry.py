"""Non-perturbing telemetry: step metrics, trace windows, compile watchdog, ledger.

The reference line of this framework observed training with host-blocking
wall-clock timers (deepspeed/utils/timer.py) plus ad-hoc TensorBoard scalars —
every timed section drained the device queue, so turning on observability
CHANGED the thing being observed (it serializes exactly the async dispatch the
offload pipeline and ring schedules exploit). This module is the TPU-native
replacement: instrumentation that rides on XLA's own machinery, in four pillars.

1. **Step metrics** (``TelemetrySession.end_step``): the default path blocks
   once per step — on a loss scalar the engine fetches anyway — and derives step
   time, samples/sec and a rolling MFU from the compiled programs' own cost
   analysis. Zero extra barriers; the barrier-per-section breakdown timers
   survive only behind ``telemetry.perturbing_breakdown`` with a loud warning.
2. **Trace windows** (``on_step_begin``): config-driven
   ``jax.profiler.start_trace``/``stop_trace`` around a chosen step range, with
   ``jax.named_scope`` annotations threaded through the engines so the captured
   trace is readable. named_scope adds HLO metadata only — zero instructions
   (asserted by tests/unit/test_telemetry.py against utils/hlo.py counts).
3. **Compile watchdog** (``CompileWatchdog`` + ``_WatchedJit``): every engine
   jit runs through an AOT-caching proxy keyed by the abstract input signature,
   so each compile is observed exactly — wall time, ``memory_analysis()``
   argument/output/temp bytes, ``cost_analysis()`` flops, and the program's
   collective wire bytes (utils/hlo.py) — and recompile storms (the classic
   silent TPU perf killer) warn by name.
4. **Resource ledger**: per-step ``device.memory_stats()`` HBM in-use/peak
   watermarks and collective wire bytes actually executed, emitted as scalars
   through ``SummaryMonitor`` (JSONL always, TensorBoard when available).
"""

import atexit
import os
import time
from collections import deque
from typing import Any, Dict, Optional

import jax
import numpy as np

from .logging import logger


def _abstract_signature(args) -> tuple:
    """Per-leaf (shape, dtype, sharding) signature of a call's inputs — the
    compile-cache key jit itself retraces on. Shardings are hashable jax objects;
    host arrays carry ``None`` (they adopt the compiled program's layout)."""
    sig = []
    for leaf in jax.tree_util.tree_leaves(args):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            a = np.asarray(leaf)
            shape, dtype = a.shape, a.dtype
        sig.append((tuple(shape), dtype, getattr(leaf, "sharding", None)))
    return tuple(sig)


_mem_unavailable_warned = set()   # backends already named in a warning


def _analyze_compiled(compiled, slice_sets=None):
    """(flops, argument/output/temp bytes, collective wire bytes, wire bytes
    split (ici, dcn), mem_unavailable) of a compiled executable, each 0 when
    the backend doesn't report it. With no slice factorization every wire
    byte accounts as ICI. ``mem_unavailable`` is True when
    ``memory_analysis()`` raised or returned nothing — recorded so its zeros
    are distinguishable from a genuinely zero-byte program, with one warning
    per backend per session instead of a silent pass."""
    flops = 0.0
    arg_b = out_b = tmp_b = wire = wire_ici = wire_dcn = 0
    mem_unavailable = False
    try:
        ca = compiled.cost_analysis()
        if not isinstance(ca, dict):  # older jax returned [dict]
            ca = ca[0] if ca else {}
        flops = max(float(ca.get("flops", 0.0)), 0.0)
    except Exception:
        pass
    try:
        mem = compiled.memory_analysis()
        if mem is None:
            raise RuntimeError("memory_analysis() returned None")
        arg_b = int(getattr(mem, "argument_size_in_bytes", 0) or 0)
        out_b = int(getattr(mem, "output_size_in_bytes", 0) or 0)
        tmp_b = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
    except Exception as e:
        mem_unavailable = True
        backend = "unknown"
        try:
            backend = jax.default_backend()
        except Exception:
            pass
        if backend not in _mem_unavailable_warned:
            _mem_unavailable_warned.add(backend)
            logger.warning(
                f"[deepspeed_tpu] telemetry: compiled memory_analysis is "
                f"unavailable on the {backend!r} backend ({e!r}); compile "
                f"records carry mem_unavailable=True and zero arg/out/temp "
                f"bytes (watermark-based HBM attribution is off)")
    try:
        from .hlo import collective_bytes, collective_axis_bytes
        text = compiled.as_text()
        wire = collective_bytes(text)
        wire_ici = wire
        if slice_sets and len(slice_sets) > 1:
            split = collective_axis_bytes(text, slice_sets)
            wire_ici, wire_dcn = split["ici"], split["dcn"]
    except Exception:
        pass
    return (flops, arg_b, out_b, tmp_b, wire, wire_ici, wire_dcn,
            mem_unavailable)


class CompileRecord:
    """One observed compile of one program signature."""

    __slots__ = ("signature", "compile_seconds", "flops", "argument_bytes",
                 "output_bytes", "temp_bytes", "wire_bytes", "wire_bytes_ici",
                 "wire_bytes_dcn", "mem_unavailable", "count")

    def __init__(self, signature, compile_seconds, flops=0.0, argument_bytes=0,
                 output_bytes=0, temp_bytes=0, wire_bytes=0, wire_bytes_ici=0,
                 wire_bytes_dcn=0, mem_unavailable=False):
        self.signature = signature
        self.compile_seconds = compile_seconds
        self.flops = flops
        self.argument_bytes = argument_bytes
        self.output_bytes = output_bytes
        self.temp_bytes = temp_bytes
        self.wire_bytes = wire_bytes
        self.wire_bytes_ici = wire_bytes_ici
        self.wire_bytes_dcn = wire_bytes_dcn
        self.mem_unavailable = mem_unavailable  # memory_analysis absent: the
        # zero arg/out/temp bytes above mean "not reported", not "zero bytes"
        self.count = 1


class CompileWatchdog:
    """Registry of every observed jit compile, keyed (program name, abstract
    input signature). A program accumulating ``recompile_warn`` distinct
    signatures warns once by name — recompiles are silent on TPU and can
    dominate wall-clock without ever surfacing in step timings."""

    def __init__(self, recompile_warn: int = 3):
        self.recompile_warn = max(int(recompile_warn), 2)
        self.records: Dict[str, Dict[tuple, CompileRecord]] = {}
        self._storm_warned = set()
        # slice factorization for the per-axis (ICI vs DCN) wire-byte split;
        # None means single-slice — every collective byte accounts as ICI
        self.slice_sets = None

    def record(self, name: str, sig, seconds: float, compiled=None) -> CompileRecord:
        per = self.records.setdefault(name, {})
        rec = per.get(sig)
        if rec is not None:  # same-signature recompile (e.g. fallback jit cache miss)
            rec.count += 1
            rec.compile_seconds += seconds
        else:
            if compiled is not None:
                (flops, arg_b, out_b, tmp_b, wire, wire_ici, wire_dcn,
                 mem_unavail) = _analyze_compiled(compiled, self.slice_sets)
            else:
                flops = arg_b = out_b = tmp_b = wire = wire_ici = wire_dcn = 0
                mem_unavail = False
            rec = per[sig] = CompileRecord(sig, seconds, flops, arg_b, out_b,
                                           tmp_b, wire, wire_ici, wire_dcn,
                                           mem_unavail)
        n = sum(r.count for r in per.values())
        if len(per) >= self.recompile_warn and name not in self._storm_warned:
            self._storm_warned.add(name)
            logger.warning(
                f"[deepspeed_tpu] telemetry: recompile storm — program {name!r} has "
                f"compiled {n} times ({len(per)} distinct input signatures, "
                f"{self.compile_seconds(name):.1f} s total). Varying shapes/dtypes/"
                f"shardings are reaching the jitted step; pad or bucket them.")
        return rec

    def compiles(self, name: Optional[str] = None) -> int:
        per = ([self.records.get(name, {})] if name is not None
               else self.records.values())
        return sum(r.count for d in per for r in d.values())

    def recompiles(self, name: Optional[str] = None) -> int:
        """Compiles beyond each program's first — the waste the watchdog hunts."""
        names = [name] if name is not None else list(self.records)
        return sum(max(self.compiles(n) - 1, 0) for n in names)

    def compile_seconds(self, name: Optional[str] = None) -> float:
        per = ([self.records.get(name, {})] if name is not None
               else self.records.values())
        return sum(r.compile_seconds for d in per for r in d.values())

    def peak_temp_bytes(self) -> int:
        return max((r.temp_bytes for d in self.records.values()
                    for r in d.values()), default=0)


class _WatchedJit:
    """Watchdog proxy around one jitted program: executes through per-signature
    AOT-compiled executables so every compile is timed and analyzed exactly, and
    every execution feeds the session's flops / wire-bytes counters. Adds no
    device work — the executable is the same one jit would run. If AOT
    lowering/execution is unsupported for this program (host callbacks etc.) the
    proxy falls back permanently to the raw jit, keeping signature tracking."""

    def __init__(self, name: str, jitted, session: "TelemetrySession"):
        self._name = name
        self._jit = jitted
        self._session = session
        self._cache: Dict[tuple, tuple] = {}
        self._fallback = False

    def lower(self, *args, **kwargs):  # flops_profiler / hlo audits delegate
        return self._jit.lower(*args, **kwargs)

    def _call_fallback(self, sig, *args):
        per = self._session.watchdog.records.get(self._name, {})
        if sig in per:
            return self._jit(*args)
        # first call on a new signature pays the compile inside the dispatch;
        # the timed wall includes one execution (upper bound, noted as opaque)
        t0 = time.perf_counter()
        out = self._jit(*args)
        self._session.watchdog.record(self._name, sig,
                                      time.perf_counter() - t0)
        return out

    def __call__(self, *args):
        sig = _abstract_signature(args)
        if self._fallback:
            return self._call_fallback(sig, *args)
        entry = self._cache.get(sig)
        if entry is None:
            t0 = time.perf_counter()
            try:
                compiled = self._jit.lower(*args).compile()
            except Exception as e:
                self._fallback = True
                logger.warning(f"[deepspeed_tpu] telemetry: AOT compile unavailable "
                               f"for program {self._name!r} ({e!r}); falling back to "
                               "the raw jit (signature tracking only)")
                return self._call_fallback(sig, *args)
            rec = self._session.watchdog.record(
                self._name, sig, time.perf_counter() - t0, compiled)
            entry = self._cache[sig] = (compiled, rec.flops, rec.wire_bytes,
                                        rec.wire_bytes_ici, rec.wire_bytes_dcn)
        compiled, flops, wire, wire_ici, wire_dcn = entry
        try:
            out = compiled(*args)
        except Exception as e:
            self._fallback = True
            self._cache.clear()
            logger.warning(f"[deepspeed_tpu] telemetry: AOT execution failed for "
                           f"program {self._name!r} ({e!r}); falling back to the "
                           "raw jit (signature tracking only)")
            return self._jit(*args)
        self._session.note_execution(flops, wire, wire_ici, wire_dcn)
        return out


def hbm_stats() -> Optional[Dict[str, int]]:
    """device 0's memory_stats dict, or None where the backend doesn't report
    them (CPU returns None; TPU/GPU report bytes_in_use / peak_bytes_in_use).
    Thin alias of utils/hbm.device_memory_stats — the package's single
    memory_stats read."""
    from .hbm import device_memory_stats
    return device_memory_stats()


class TelemetrySession:
    """One engine's telemetry: watchdog-wrapped programs, per-step scalars
    through a SummaryMonitor, and the configured profiler trace window.

    ``monitor``: an existing SummaryMonitor to emit through; when None, the
    session opens its own at ``output_path``/``job_name`` (scalars.jsonl always;
    TensorBoard when importable)."""

    def __init__(self, monitor=None, peak_tflops: Optional[float] = None,
                 trace_dir: Optional[str] = None, trace_steps=None,
                 mfu_window: int = 20, recompile_warn: int = 3,
                 output_path: Optional[str] = None, job_name: Optional[str] = None,
                 run_id: Optional[str] = None,
                 host_id: Optional[int] = None):
        self.watchdog = CompileWatchdog(recompile_warn=recompile_warn)
        self.peak_tflops = float(peak_tflops) if peak_tflops else None
        self.trace_dir = trace_dir or "deepspeed_telemetry_trace"
        # namespaced trace output (mirrors the flight-recorder dump naming):
        # trace_<run>_host<h>/ under trace_dir, so two engines sharing one
        # trace_dir never interleave profiler sessions. run_id="" opts back
        # into the legacy layout (the trace lands in trace_dir itself);
        # run_id=None derives the same default id the flight recorder uses.
        if run_id is None:
            from .numerics import default_run_id
            run_id = default_run_id()
        self.run_id = run_id
        if host_id is None:
            try:
                host_id = jax.process_index()
            except Exception:
                host_id = 0
        self.host_id = int(host_id)
        self.trace_output_dir = (
            os.path.join(self.trace_dir,
                         f"trace_{self.run_id}_host{self.host_id}")
            if self.run_id else self.trace_dir)
        self.trace_steps = tuple(trace_steps) if trace_steps is not None else None
        self._owns_monitor = monitor is None
        if monitor is None:
            from .monitor import SummaryMonitor
            monitor = SummaryMonitor(output_path or None,
                                     job_name or "DeepSpeedTelemetry")
        self.monitor = monitor

        # step-metric state: everything is a host counter fed by the proxies;
        # end_step differences them — no device work, no barriers
        self.flops_executed = 0.0
        self.wire_bytes_executed = 0
        self.wire_ici_executed = 0
        self.wire_dcn_executed = 0
        self.steps_recorded = 0
        self.last_mfu = None
        self.last_step_ms = None
        self.last_dispatch_ms = None
        self._dispatch_base = None
        self.last_wire_bytes = 0
        self.last_wire_bytes_ici = 0
        self.last_wire_bytes_dcn = 0
        self._dispatch_mark = None
        self._window = deque(maxlen=max(int(mfu_window), 1))  # (dt, flops)
        self._last_end = time.perf_counter()
        self._last_flops = 0.0
        self._last_wire = 0
        self._last_wire_ici = 0
        self._last_wire_dcn = 0
        self._last_compiles = 0

        # HBM observatory (docs/hbm.md): per-class resident bytes from the
        # engine's memory_manifest — host dicts only, set once at wiring time,
        # emitted as Memory/* scalars in end_step (no device work ever)
        self._memory_class_bytes = None
        self._memory_geometry = None
        self._forecast_config = None

        self._trace_active = False
        self._trace_done = False
        self._trace_failed = False
        self._warned_perturbing = False
        self._noted_suppressed = False
        self._closed = False
        atexit.register(self.close)

    # ------------------------------------------------------------- watchdog
    def watch(self, name: str, jitted):
        """Wrap a jitted program in the compile watchdog (None passes through)."""
        if jitted is None:
            return None
        return _WatchedJit(name, jitted, self)

    def note_execution(self, flops: float, wire_bytes: int,
                       wire_ici: int = 0, wire_dcn: int = 0):
        self.flops_executed += flops
        self.wire_bytes_executed += wire_bytes
        self.wire_ici_executed += wire_ici
        self.wire_dcn_executed += wire_dcn

    def set_memory_manifest(self, class_bytes, geometry=None,
                            forecast_config=None):
        """Install the engine's per-class resident-byte attribution
        (utils/hbm.manifest_signatures over engine.memory_manifest()).
        ``class_bytes`` is a host dict {class: per-device bytes}; ``geometry``
        the manifest's predictor geometry; ``forecast_config`` an optional
        utils/hbm.forecast config enabling fitting-delta suggestions in the
        flight recorder's OOM forensics. Pure host state — end_step emits the
        classes as ``Memory/*`` scalars and nothing about the compiled step
        changes (HLO-instruction-identity is pinned in tests)."""
        self._memory_class_bytes = dict(class_bytes) if class_bytes else None
        self._memory_geometry = dict(geometry) if geometry else None
        self._forecast_config = forecast_config

    def memory_snapshot(self) -> Optional[Dict[str, Any]]:
        """The OOM-forensics input: manifest classes + geometry + the device
        watermarks + the watchdog's compiled-temp peak. None when no manifest
        was installed (telemetry.hbm off)."""
        if self._memory_class_bytes is None:
            return None
        return {
            "classes": dict(self._memory_class_bytes),
            "geometry": dict(self._memory_geometry or {}),
            "measured": hbm_stats(),
            "temp_peak_bytes": self.watchdog.peak_temp_bytes(),
            "forecast_config": self._forecast_config,
        }

    def set_comm_topology(self, slice_sets):
        """Install the slice factorization (list of per-slice device-id sets,
        CommTopology.slice_device_sets) that splits every subsequently compiled
        program's wire bytes into the ICI vs DCN ledger. Call before the step
        programs compile — already-analyzed records keep their old split."""
        self.watchdog.slice_sets = (
            [frozenset(s) for s in slice_sets] if slice_sets else None)

    # ------------------------------------------------------------- trace window
    def on_step_begin(self, global_step: int):
        """Trace-window bookkeeping; called at the first micro-step of a window
        with the number of COMPLETED optimizer steps (captures steps a..b-1 for
        ``trace_steps = [a, b]``)."""
        if self.trace_steps is None or self._trace_failed:
            return
        a, b = self.trace_steps
        if self._trace_active and global_step >= b:
            self._stop_trace()
        if not self._trace_active and not self._trace_done and a <= global_step < b:
            self._start_trace()

    def _start_trace(self):
        a, b = self.trace_steps
        try:
            os.makedirs(self.trace_output_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_output_dir)
        except Exception as e:
            self._trace_failed = True
            logger.warning(f"[deepspeed_tpu] telemetry: profiler trace unavailable "
                           f"({e!r}); trace window [{a}, {b}) skipped")
            return
        self._trace_active = True
        logger.info(f"[deepspeed_tpu] telemetry: profiler trace started for steps "
                    f"{a}..{b - 1} -> {self.trace_output_dir}")

    def _stop_trace(self):
        try:
            jax.profiler.stop_trace()
            logger.info(f"[deepspeed_tpu] telemetry: profiler trace written to "
                        f"{self.trace_output_dir}")
        except Exception as e:
            self._trace_failed = True
            logger.warning(f"[deepspeed_tpu] telemetry: stop_trace failed ({e!r})")
        self._trace_active = False
        self._trace_done = True

    # ------------------------------------------------------------- step metrics
    def mark_step_dispatched(self):
        """Host-local step boundary: the engine calls this when every
        host-side phase of the step is done and it is about to dispatch the
        final update program — i.e. when this host ARRIVES at the step's
        barrier. end_step turns it into ``last_dispatch_ms``. The cluster
        observatory attributes stragglers from this window: collectives (and
        the fetches behind them) equalise the end-to-end step wall across
        hosts, so only how LATE a host reached the barrier shows which host
        was actually slow."""
        self._dispatch_mark = time.perf_counter()

    def rebase_dispatch_window(self):
        """Restart the host-local dispatch window NOW. The cluster observatory
        calls this right after its heartbeat allgather: the allgather is
        itself a cross-host rendezvous, so time spent waiting in it belongs to
        the slow peer — charging it to THIS host's next dispatch window would
        re-equalise exactly the signal the window exists to separate."""
        self._dispatch_base = time.perf_counter()

    def end_step(self, global_step: int, samples_per_step: int, pending=None,
                 numerics=None, goodput=None, serving=None,
                 schedule_goodput=None, run_goodput=None):
        """Close one optimizer step's metrics. The ONLY blocking operation is a
        device_get of ``pending``'s last loss scalar (already computed; the
        engine fetches it for its monitor anyway) — the step boundary rides that
        fetch instead of a queue-draining barrier, so the offload/ring pipelines
        stay fully async. ``global_step`` is the count of completed steps.

        ``numerics`` (optional) is the step's in-graph sentinel output (a small
        pytree of per-subtree stat vectors); it is fetched JOINTLY with the loss
        in the same device_get, so enabling the numerics sentinel adds no host
        sync point. Returns the host-side numerics stats (or None).

        ``schedule_goodput`` (optional) is the pipeline tracer's per-step
        schedule decomposition (utils/pipeline_trace.goodput_decomposition) —
        already computed from host timestamps, so emitting it here adds
        ``Pipeline/Goodput/*`` scalars only. ``goodput`` is its deprecated
        alias (one release; the bare name collided with the run-level ledger).

        ``run_goodput`` (optional) is the run-lifecycle ledger's scalar dict
        (utils/goodput.RunLedger.scalar_items) — emitted verbatim as
        ``Run/Goodput/*`` scalars. The two fractions measure different
        things: Pipeline/Goodput is schedule efficiency within one step,
        Run/Goodput is productive wall over the whole run (docs/goodput.md).

        ``serving`` (optional) is the serving request tracer's flat latency
        summary (serve/request_trace.RequestTracer.latency_summary — e.g.
        ``ttft_ms_p99``); emitted as ``Serving/Latency/*`` scalars, again
        host-computed so scalars only."""
        if schedule_goodput is None:
            schedule_goodput = goodput
        # dispatch boundary: set by mark_step_dispatched (engine, pre-fetch);
        # a caller that never marks gets "now", i.e. dispatch wall == step wall
        fetch_start = self._dispatch_mark
        if fetch_start is None:
            fetch_start = time.perf_counter()
        self._dispatch_mark = None
        numerics_host = None
        try:
            if pending:
                _, numerics_host = jax.device_get((pending[-1], numerics))
            elif numerics is not None:
                numerics_host = jax.device_get(numerics)
        except Exception:
            pass
        now = time.perf_counter()
        compiles = self.watchdog.compiles()
        dt = now - self._last_end
        dispatch_base = (self._dispatch_base if self._dispatch_base is not None
                         else self._last_end)
        dispatch_dt = fetch_start - dispatch_base
        self._dispatch_base = None
        flops_d = self.flops_executed - self._last_flops
        wire_d = self.wire_bytes_executed - self._last_wire
        wire_ici_d = self.wire_ici_executed - self._last_wire_ici
        wire_dcn_d = self.wire_dcn_executed - self._last_wire_dcn
        had_compile = compiles != self._last_compiles
        self._last_end = now
        self._last_flops = self.flops_executed
        self._last_wire = self.wire_bytes_executed
        self._last_wire_ici = self.wire_ici_executed
        self._last_wire_dcn = self.wire_dcn_executed
        self._last_compiles = compiles

        samples = global_step * samples_per_step
        mon = self.monitor
        self.last_step_ms = dt * 1000.0
        self.last_dispatch_ms = max(dispatch_dt, 0.0) * 1000.0
        self.last_wire_bytes = wire_d
        self.last_wire_bytes_ici = wire_ici_d
        self.last_wire_bytes_dcn = wire_dcn_d
        self.steps_recorded += 1
        mon.add_scalar("Telemetry/Samples/step_time_ms", dt * 1000.0, samples)
        if dt > 0:
            mon.add_scalar("Telemetry/Samples/samples_per_sec",
                           samples_per_step / dt, samples)
        mon.add_scalar("Telemetry/Samples/wire_bytes", wire_d, samples)
        mon.add_scalar("Telemetry/Samples/wire_bytes_ici", wire_ici_d, samples)
        mon.add_scalar("Telemetry/Samples/wire_bytes_dcn", wire_dcn_d, samples)
        # rolling MFU over compile-free steps: a step that paid a compile would
        # poison the window with compile wall-time that is not execution
        if not had_compile and flops_d > 0 and dt > 0:
            self._window.append((dt, flops_d))
        if self.peak_tflops and self._window:
            from .flops_profiler import mfu as _mfu
            tot_dt = sum(d for d, _ in self._window)
            tot_f = sum(f for _, f in self._window)
            self.last_mfu = _mfu({"flops": tot_f}, tot_dt, self.peak_tflops)
            mon.add_scalar("Telemetry/Samples/mfu", self.last_mfu, samples)
        stats = hbm_stats()
        if stats is not None:
            mon.add_scalar("Telemetry/Samples/hbm_in_use_bytes",
                           stats.get("bytes_in_use", 0), samples)
            mon.add_scalar("Telemetry/Samples/hbm_peak_bytes",
                           stats.get("peak_bytes_in_use", 0), samples)
        mon.add_scalar("Telemetry/Samples/compile_count", compiles, samples)
        # per-class resident-HBM attribution: host constants installed once by
        # the engine via set_memory_manifest — no device syncs, and the
        # compiled step is untouched (HLO-instruction-identity pinned in
        # tests). Scalars appear/disappear with telemetry.hbm only.
        if self._memory_class_bytes is not None:
            for cls, nbytes in sorted(self._memory_class_bytes.items()):
                mon.add_scalar(f"Memory/{cls}_bytes", nbytes, samples)
            mon.add_scalar("Memory/compiled_temp_peak_bytes",
                           self.watchdog.peak_temp_bytes(), samples)
        if schedule_goodput:
            for key in ("fwd_seconds", "bwd_seconds", "p2p_seconds", "load_seconds",
                        "reduce_seconds", "opt_seconds", "bubble_seconds",
                        "pipeline_seconds"):
                if key in schedule_goodput:
                    mon.add_scalar(f"Pipeline/Goodput/{key}",
                                   schedule_goodput[key], samples)
            if schedule_goodput.get("bubble_fraction") is not None:
                mon.add_scalar("Pipeline/Goodput/bubble_fraction",
                               schedule_goodput["bubble_fraction"], samples)
        if run_goodput:
            for key in sorted(run_goodput):   # sorted: deterministic order
                mon.add_scalar(key, run_goodput[key], samples)
        if serving:
            for key in sorted(serving):   # sorted: deterministic scalar order
                mon.add_scalar(f"Serving/Latency/{key}", serving[key], samples)
        mon.flush()
        if self._trace_active and self.trace_steps is not None \
                and global_step >= self.trace_steps[1]:
            self._stop_trace()
        return numerics_host

    # ------------------------------------------------------------- breakdown gate
    def warn_perturbing_once(self):
        if not self._warned_perturbing:
            self._warned_perturbing = True
            logger.warning(
                "[deepspeed_tpu] telemetry.perturbing_breakdown=true: barrier-per-"
                "section timers are ACTIVE — every section boundary drains the "
                "device queue (jax.effects_barrier), serializing async dispatch and "
                "the offload/ring pipelines. The numbers are for debugging section "
                "attribution only; disable for performance runs.")

    def note_breakdown_suppressed_once(self):
        if not self._noted_suppressed:
            self._noted_suppressed = True
            logger.info(
                "[deepspeed_tpu] telemetry: wall_clock_breakdown=true is suppressed "
                "while telemetry is enabled (its per-section barriers would perturb "
                "the run being measured); set telemetry.perturbing_breakdown=true "
                "to force the breakdown timers anyway.")

    # ------------------------------------------------------------- reporting
    def summary(self) -> Dict[str, Any]:
        """One-shot digest for benches/reports: rolling MFU, HBM watermarks,
        wire bytes of the last step, and the watchdog's compile accounting."""
        stats = hbm_stats() or {}
        # trace-window disposition, with the _trace_failed latch surfaced so
        # a "profiler unavailable" run is visible in every bench/report
        # digest instead of only in one early warning line
        trace = None
        if self.trace_steps is not None:
            trace = {
                "trace_dir": self.trace_output_dir,
                "steps": list(self.trace_steps),
                "active": self._trace_active,
                "done": self._trace_done,
                "failed": self._trace_failed,
            }
        return {
            "mfu": self.last_mfu,
            "step_time_ms": self.last_step_ms,
            "steps_recorded": self.steps_recorded,
            "trace": trace,
            "wire_bytes_per_step": self.last_wire_bytes,
            "wire_bytes_per_step_ici": self.last_wire_bytes_ici,
            "wire_bytes_per_step_dcn": self.last_wire_bytes_dcn,
            "hbm_in_use_bytes": int(stats.get("bytes_in_use", 0)),
            "hbm_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
            "compile_count": self.watchdog.compiles(),
            "recompile_count": self.watchdog.recompiles(),
            "compile_seconds": round(self.watchdog.compile_seconds(), 3),
            "compiled_temp_bytes_peak": self.watchdog.peak_temp_bytes(),
        }

    def close(self):
        if self._closed:
            return
        self._closed = True
        if self._trace_active:
            self._stop_trace()
        if self._owns_monitor and self.monitor is not None:
            self.monitor.close()
