"""The program's own span and counter recorder.

One process-wide ``Recorder`` (``spans.recorder()``): a bounded in-memory ring of spans
on ``time.perf_counter`` and a dict of counters that are never evicted. It is always on.
It has no config key, no environment variable and writes no file: a handful of spans a
step costs microseconds, and the last minutes of any run are there for whoever asks.

Every span is also entered as a ``jax.profiler.TraceAnnotation`` of the same name. That
is inert unless a profiler session runs; when one does, the span lands in the profiler's
own file on the device's clock, above the device rows. Nothing here waits for the device,
fetches from it or adds to a compiled program.

    rec = spans.recorder()
    with rec.span("train.put_batch", engine=eid, step=n):
        ...
    rec.spans()        # the ring, oldest first
    rec.counters(eid)  # {"program.builds[loss_and_grad]": 2, ...}
    rec.programs(eid)  # lazy: {program: {"module": ..., "ops": {instruction: op_name},
                       #                  "memory": {"argument": bytes, ..., "code": bytes},
                       #                  "cost": {instruction: [flops, bytes]}, "products": {...},
                       #                  "collectives": [instruction, ...]}}

A span's parent is the innermost span of its engine that was open on the same thread
when it began (two engines may take turns on one thread), or of any engine if it names
none. Beside its wall seconds a span keeps the CPU seconds of its thread (``cpu_s``,
``time.thread_time`` at ``begin`` and ``end``, children included): wall less CPU is the
time the thread was held, by the runtime inside a program call or by the operating system
anywhere. The synthetic ``compile.*`` spans carry none. The four ``compile.*`` spans come from ``jax.monitoring``'s duration events, which
arrive when the work is over: each is put down as ``[now - seconds, now]`` under whichever
span is open, so a build lands in the step and the program call that caused it.
"""

import collections
import itertools
import threading
import time
import weakref

import jax

clock = time.perf_counter
cpu_clock = time.thread_time          # the calling thread's CPU seconds, user and system

RING_SPANS = 8192                   # some five minutes of one-program-pair steps
RING_DEVICE_SCALARS = 1024          # steps whose device scalars are kept, a few bytes each
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_load",
}
BUILD_SPAN = "compile.backend"      # one a built or loaded executable
MEMORY_SIZES = {"argument": "argument_size_in_bytes", "output": "output_size_in_bytes",
                "alias": "alias_size_in_bytes", "temp": "temp_size_in_bytes",
                "code": "generated_code_size_in_bytes"}      # of ``memory_analysis()``
MIN_COMPILE_SPAN_S = 1e-3           # shorter ones are counted and not kept: the inner jits
                                    # of one trace come by the thousand and would empty the ring


class Span:
    __slots__ = ("id", "parent", "engine", "name", "start", "end", "step", "attrs",
                 "cpu_s", "_cpu_start", "_annotation")

    def __init__(self, id, parent, engine, name, start, step, attrs):
        self.id, self.parent, self.engine, self.name = id, parent, engine, name
        self.start, self.end, self.step, self.attrs = start, None, step, attrs
        self.cpu_s = self._cpu_start = self._annotation = None

    def as_dict(self):
        return {"id": self.id, "parent": self.parent, "engine": self.engine,
                "name": self.name, "start": self.start, "end": self.end,
                "cpu_s": self.cpu_s,
                "step": self.step, "attrs": dict(self.attrs) if self.attrs else {}}


class _Scope:
    """``with recorder.span(...) as span``."""
    __slots__ = ("_recorder", "_span")

    def __init__(self, recorder, span):
        self._recorder, self._span = recorder, span

    def __enter__(self):
        return self._span

    def __exit__(self, *exc):
        self._recorder.end(self._span)
        return False


class Recorder:
    def __init__(self, capacity=RING_SPANS):
        self._ring = collections.deque(maxlen=capacity)
        self._ids = itertools.count(1)
        self._engines = itertools.count(1)
        self._open = threading.local()
        self._lock = threading.Lock()
        self._counters = {}          # (engine, name) -> int
        self._device_scalars = collections.deque(maxlen=RING_DEVICE_SCALARS)
        self._programs = weakref.WeakValueDictionary()   # engine -> the Programs it holds

    # ------------------------------------------------------------------ spans
    def new_engine(self, programs=None):
        """An id for one engine's spans and counters; several engines share a process.
        ``programs`` is the engine's own ``Programs``: the recorder only refers to it
        weakly, so the compiled step programs go when their engine goes."""
        engine = next(self._engines)
        if programs is not None:
            self._programs[engine] = programs
        return engine

    def _stack(self):
        try:
            return self._open.stack
        except AttributeError:
            stack = self._open.stack = []
            return stack

    def begin(self, name, engine=None, step=None, root=False, **attrs):
        """Open a span that ``end`` closes; for spans that outlive one function. A
        ``root`` span has no parent, whatever another caller left open."""
        stack = self._stack()
        parent = None
        if not root:
            parent = next((s for s in reversed(stack)
                           if engine is None or s.engine == engine), None)
        if parent is not None:
            if engine is None:
                engine = parent.engine
            if step is None:
                step = parent.step
        span = Span(next(self._ids), parent.id if parent else None, engine, name,
                    clock(), step, attrs or None)
        span._annotation = jax.profiler.TraceAnnotation(name)
        span._annotation.__enter__()
        stack.append(span)
        span._cpu_start = cpu_clock()
        return span

    def end(self, span):
        """Close ``span`` and whatever was left open inside it: its descendants, and not
        another engine's step that was opened after it."""
        stack = self._stack()
        if span not in stack:
            return
        at = stack.index(span)
        inside, others = {span.id}, []
        for s in stack[at + 1:]:
            if s.parent in inside:
                inside.add(s.id)
            else:
                others.append(s)
        closing = [s for s in stack[at:] if s.id in inside]
        stack[at:] = others
        for s in reversed(closing):
            s.cpu_s = cpu_clock() - s._cpu_start
            s._annotation.__exit__(None, None, None)
            s._annotation = None
            s.end = clock()
            self._ring.append(s)

    def span(self, name, engine=None, step=None, **attrs):
        return _Scope(self, self.begin(name, engine, step, **attrs))

    def spans(self, engine=None):
        """The closed spans in the ring, oldest first, as dicts."""
        return [s.as_dict() for s in list(self._ring) if engine is None or s.engine == engine]

    # --------------------------------------------------------------- counters
    def count(self, engine, name):
        with self._lock:
            self._counters[engine, name] = self._counters.get((engine, name), 0) + 1

    def counters(self, engine):
        with self._lock:
            return {name: v for (e, name), v in self._counters.items() if e == engine}

    def _program_call(self):
        """The innermost open span of this thread that is a step program's call, or None."""
        return next((s for s in reversed(self._stack()) if s.attrs and "program" in s.attrs), None)

    def count_in_program(self, name, what=""):
        """Count ``<name>[<program>]<what>`` against the engine whose step program's call is open
        on this thread: for what a program leaves WHILE IT IS TRACED (its Python runs inside the
        call that builds it), once a trace; nothing outside such a call."""
        owner = self._program_call()
        if owner is not None:
            self.count(owner.engine, f"{name}[{owner.attrs['program']}]{what}")

    # --------------------------------------------------------- device scalars
    def keep_device_scalars(self, engine, step, scalars):
        """Keep a step's device scalars (a dict of arrays the step program returned beside
        its loss) as they are, unfetched: nothing here waits for the device."""
        self._device_scalars.append((engine, step, scalars))

    def device_scalars(self, engine):
        """``[(step, {name: device array})]`` of the steps still kept, oldest first, as
        unfetched as they were kept: the caller fetches (``jax.device_get``), after a
        measured window and never inside one."""
        return [(step, scalars) for e, step, scalars in list(self._device_scalars) if e == engine]

    # ---------------------------------------------------------------- compiles
    def on_compile_event(self, event, seconds, **kwargs):
        """A ``jax.monitoring`` duration listener: one ``compile.*`` span an event, and a
        build counted against the step program whose call is open."""
        name = COMPILE_EVENTS.get(event)
        if name is None:
            return
        stack = self._stack()
        if seconds >= MIN_COMPILE_SPAN_S:
            parent = stack[-1] if stack else None
            now = clock()
            span = Span(next(self._ids), parent.id if parent else None,
                        parent.engine if parent else None, name, now - seconds,
                        parent.step if parent else None,
                        {"fun_name": kwargs["fun_name"]} if kwargs.get("fun_name") else None)
            span.end = now
            self._ring.append(span)
        if name == BUILD_SPAN:
            owner = self._program_call()
            if owner is not None:
                owner.attrs["builds"] = owner.attrs.get("builds", 0) + 1
                self.count(owner.engine, f"program.builds[{owner.attrs['program']}]")

    # ---------------------------------------------------------------- programs
    def programs(self, engine):
        """The catalog of ``engine``'s step programs (``Programs.catalog``); nothing for
        an engine that is gone or never gave its ``Programs``."""
        held = self._programs.get(engine)
        return held.catalog() if held is not None else {}


class Programs:
    """One engine's step programs as last built, and the catalog made from them on
    request. The engine holds it (``Recorder.new_engine``): it goes with its engine."""

    def __init__(self):
        self._kept = {}              # program -> (jitted, abstract arguments)
        self._catalog = {}           # program -> {"module", "ops", "memory", "cost", ...}

    def keep(self, program, jitted, args):
        """Remember how ``program`` was last built, so that ``catalog`` can ask the
        compiler for its text later. Shapes only: no argument is kept alive."""
        def abstract(x):
            if not isinstance(x, jax.Array):
                return x
            return jax.ShapeDtypeStruct(x.shape, x.dtype, weak_type=x.aval.weak_type,
                                        sharding=x.sharding if x.committed else None)

        self._kept[program] = (jitted, jax.tree_util.tree_map(abstract, args))
        self._catalog.pop(program, None)

    def catalog(self):
        """``{program: {"module": HloModule name, "ops": {instruction: op_name}, "memory":
        {"argument", "output", "alias", "temp", "code": bytes}, "cost": {instruction:
        [flops, bytes]}, "products": {instruction: {"mkn": [[M, K, N, types], ...], "as":
        ...}}, "collectives": [instruction, ...]}}`` for the step programs the engine has run: every instruction of the
        optimized program with the scope path JAX gave it ("" where the compiler made it
        up); the program's own need of device memory as the compiler states it
        (``memory_analysis()``; None where the backend states none); and what every
        operation the device runs on its own HAS to do, its products' operations and a
        floor of its HBM bytes, read from the same text (``hlo.instruction_costs``: counts,
        no peak; kernels and collectives are absent from ``cost``, and ``collectives`` names
        the instructions that are one or only wrap one). Computed on request and kept: it
        compiles (or loads from the persistent cache), so nobody asks inside a measured
        window."""
        from . import hlo
        for program, (jitted, args) in self._kept.items():
            if program not in self._catalog:
                compiled = jitted.lower(*args).compile()
                text = compiled.as_text()
                ops = dict.fromkeys(hlo.instruction_names(text), "")
                ops.update(hlo.instruction_op_names(text))
                self._catalog[program] = {"module": hlo.module_name(text), "ops": ops,
                                          "memory": _memory_sizes(compiled),
                                          **hlo.instruction_costs(text)}
        return dict(self._catalog)


def _memory_sizes(compiled):
    """``compiled.memory_analysis()`` as a dict of bytes a device; None where it has none."""
    stated = compiled.memory_analysis()
    if stated is None:
        return None
    return {name: int(getattr(stated, field)) for name, field in MEMORY_SIZES.items()}


_RECORDER = None
_RECORDER_LOCK = threading.Lock()


def recorder():
    """The process's recorder; the first call makes it and registers its compile
    listener with ``jax.monitoring``, once."""
    global _RECORDER
    if _RECORDER is None:
        with _RECORDER_LOCK:
            if _RECORDER is None:
                made = Recorder()
                jax.monitoring.register_event_duration_secs_listener(made.on_compile_event)
                _RECORDER = made
    return _RECORDER
