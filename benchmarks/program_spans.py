"""From the program's own spans, counters and scope names to per-layer numbers.

The program (``deepspeed_tpu/utils/spans.py``) supplies spans on ``perf_counter``,
counters, and for each step program the scope path of every instruction. The reduction
is the benchmark's and lives here; the readers in ``layer_metrics/`` each return one
number of ``analyse(record)``, which is worked out once a record.

    host side     the window's ``train.step`` spans, their children and self times,
                  the builds counted and the seconds of the calls that built
    shared clock  the harness's ``dispatch`` spans end on the trace's clock where the
                  record's step returns lie on ``perf_counter``; the median difference
                  is the offset
    idle          every device idle gap, cut at span boundaries, goes to the innermost
                  program span that covers it, or to ``caller``
    device        every operation gets the step program that holds its instruction
                  name (a shared name goes with its neighbours), then a phase
                  (optimizer, backward, forward), a part (``ds_embed``, ``ds_attn``,
                  ``ds_mlp``, ``ds_loss``) and a kernel (``ds_flash_*``)

Against a program that has no recorder (the parent of the PR that added it), or a
record with no window, everything here returns ``None`` and raises nothing.
"""

import bisect
import json
import os
import re
import statistics

from benchmarks import trace_reduce as tr

STEP = "train.step"
CALLER = "caller"
UNASSIGNED = "unassigned"
PHASES = ("forward", "backward", "optimizer")
UPDATE_SCOPE = "ds_apply_update"
PART_RE = re.compile(r"ds_(?:embed|attn|mlp|loss)\b")
KERNEL_RE = re.compile(r"ds_flash_(?:fwd|bwd_dq|bwd_dkv)")
MAX_UNASSIGNED = 0.02              # of the window; above it the shares are not reported
OUT_NAME = "program_spans.last.json"


def program_recorder():
    """The program's recorder, or None where the program has none."""
    try:
        from deepspeed_tpu.utils import spans
    except ImportError:
        return None
    return spans.recorder()


# ----------------------------------------------------------------- host side
def self_seconds(span, children):
    """A span's duration minus what its children cover (children may overlap)."""
    covered = tr.measure(tr.clip(tr.union([[c["start"], c["end"]] for c in children]),
                                 span["start"], span["end"]))
    return (span["end"] - span["start"]) - covered


def window_steps(spans, t0, t1, slack=1e-3):
    """The ``train.step`` spans that lie in [t0, t1], of the engine that has most of
    them there (a process may hold several engines), and that engine's id."""
    inside = [s for s in spans if s["name"] == STEP
              and s["start"] >= t0 - slack and s["end"] <= t1 + slack]
    if not inside:
        return [], None
    engines = [s["engine"] for s in inside]
    engine = max(set(engines), key=engines.count)
    return sorted((s for s in inside if s["engine"] == engine), key=lambda s: s["start"]), engine


def host_table(spans, steps):
    """{span name: {"count", "median_ms", "self_median_ms"}} over the descendants of
    ``steps``, and the self seconds of each step."""
    kids = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)
    by_name, step_self = {}, []
    todo = list(steps)
    while todo:
        s = todo.pop()
        own = kids.get(s["id"], [])
        todo.extend(own)
        mine = self_seconds(s, own)
        by_name.setdefault(s["name"], []).append((s["end"] - s["start"], mine))
        if s["name"] == STEP:
            step_self.append(mine)
    table = {name: {"count": len(v),
                    "median_ms": 1e3 * statistics.median(d for d, _ in v),
                    "self_median_ms": 1e3 * statistics.median(m for _, m in v)}
             for name, v in sorted(by_name.items())}
    return table, step_self


def build_seconds(spans, engine, before):
    """Seconds the engine's ``train.*`` spans spent building or loading, up to
    ``before``: a program span (any ``train.*`` but the step) that holds a ``compile.*``
    child counts whole (trace, lower, compile or load, first dispatch); compile spans
    directly under a step (one-operation programs) count as themselves."""
    mine = {s["id"]: s for s in spans if s["engine"] == engine}
    whole, direct = {}, []
    for s in mine.values():
        parent = mine.get(s["parent"])
        if not s["name"].startswith("compile.") or parent is None or s["end"] > before:
            continue
        if parent["name"] == STEP:
            direct.append([s["start"], s["end"]])
        elif parent["name"].startswith("train."):
            whole[parent["id"]] = parent
    return sum(p["end"] - p["start"] for p in whole.values()) + tr.measure(tr.union(direct))


# --------------------------------------------------------------- shared clock
def clock_offset(host, returns):
    """Trace clock minus ``perf_counter``: the median, over the window's steps, of the
    end of the harness's ``dispatch`` span in the trace less the step's return in the
    record. None unless the two lists pair up one to one."""
    ends = sorted(e for name, _, e in host if name == "dispatch")
    if not ends or len(ends) != len(returns):
        return None
    return statistics.median(e - r for e, r in zip(ends, returns))


def innermost_intervals(spans):
    """{span name: disjoint intervals} in which a span of that name was the innermost
    one open. Nesting is read from the times, not from the parent ids: a cache load lies
    inside its backend compile though both name the program call as their parent."""
    out, stack, pos = {}, [], None       # stack of [name, end]

    def emit(name, lo, hi):
        if hi > lo:
            out.setdefault(name, []).append([lo, hi])

    for s in sorted(spans, key=lambda s: (s["start"], -s["end"])):
        while stack and stack[-1][1] <= s["start"]:
            name, end = stack.pop()
            emit(name, pos, end)
            pos = max(pos, end)
        if stack:
            emit(stack[-1][0], pos, s["start"])
        pos = s["start"]
        stack.append([s["name"], min(s["end"], stack[-1][1]) if stack else s["end"]])
    while stack:
        name, end = stack.pop()
        emit(name, pos, end)
        pos = max(pos, end)
    return out


def intersect(a, b):
    """The part of the disjoint sorted ``a`` that ``b`` covers."""
    return tr.subtract(a, tr.subtract(a, b))


def idle_by_span(gaps, spans, offset):
    """{span name or ``caller``: idle seconds}: the device's idle ``gaps`` (trace clock)
    cut at the boundaries of the program's spans (``perf_counter`` + ``offset``)."""
    gaps = tr.union(gaps)
    out, left = {}, gaps
    for name, ivs in innermost_intervals(spans).items():
        ivs = [[s + offset, e + offset] for s, e in ivs]
        got = tr.measure(intersect(gaps, ivs))
        if got > 0:
            out[name] = got
        left = tr.subtract(left, ivs)
    rest = tr.measure(left)
    if rest > 0:
        out[CALLER] = rest
    return out


# ---------------------------------------------------------------- device side
def instruction(name):
    """``fusion.2175`` of the trace's ``fusion.2175 bf16[6400] fusion``."""
    return name.split(" ", 1)[0]


def assign_programs(events, catalog):
    """The step program of each device operation, in time order: by instruction name
    where one program alone has it; where several have it, the program of the nearest
    operations before and after that are certain, if they agree (a program's operations
    run one after another). None otherwise: between two programs, or in no program."""
    owners = {}
    for program, info in catalog.items():
        for name in info["ops"]:
            owners.setdefault(name, set()).add(program)
    certain = []
    for name, _, _ in events:
        who = owners.get(instruction(name), ())
        certain.append(next(iter(who)) if len(who) == 1 else None)
    before, last = [], None
    for c in certain:
        last = c or last
        before.append(last)
    after, nxt = [None] * len(events), None
    for i in range(len(events) - 1, -1, -1):
        nxt = certain[i] or nxt
        after[i] = nxt
    out = []
    for i, (name, _, _) in enumerate(events):
        who = owners.get(instruction(name), ())
        if certain[i] or not who:
            out.append(certain[i])
        else:
            # at an edge of the trace one side has no certain operation: the other decides
            sides = {p for p in (before[i], after[i]) if p is not None}
            out.append(sides.pop() if len(sides) == 1 and sides <= who else None)
    return out


def phase_of(program, op_name, update_programs):
    if program in update_programs or UPDATE_SCOPE in op_name:
        return "optimizer"
    return "backward" if "transpose(" in op_name else "forward"


def kernel_of(name, op_name):
    """``ds_flash_fwd``, ``ds_flash_bwd_dq``, ``ds_flash_bwd_dkv`` or "": the kernel's
    own name where the trace prints it, else its scope."""
    if "tpu_custom_call" not in name:
        return ""
    found = KERNEL_RE.match(instruction(name)) or KERNEL_RE.search(op_name)
    return found.group(0) if found else "custom_call"


def device_table(events, busy, lo, hi, catalog, update_programs):
    """{(phase, part, kernel): seconds} of one device's operations inside [lo, hi), each
    instant counted once (an operation that starts under another is cut to what is
    left). ``events`` are the trace's innermost operations and ``busy`` the union of all
    of them, enclosing ones too: the time in which only an enclosing operation ran (a
    ``while`` between two of its body's operations; an operation the reduction took for
    one because a zero-length copy-start fell inside it) goes to the phase of the
    operations on both sides of it where they agree, under the part ``enclosing``. An
    operation the compiler made up (a copy-done, a slice: no scope path) goes with the
    scoped operation of its program before it. What no program claims goes under
    ``unassigned``."""
    events = sorted(events, key=lambda e: e[1])
    programs = assign_programs(events, catalog)
    table, reach, before = {}, lo, None
    # busy seconds up to x, by bisection: the device's busy intervals come by the
    # hundred thousand (operations a few nanoseconds apart), as the operations do
    starts, ends = [s for s, _ in busy], [e for _, e in busy]
    total = [0.0]
    for s, e in busy:
        total.append(total[-1] + (e - s))

    def busy_until(x):
        i = bisect.bisect_right(starts, x)
        return total[i] - max(0.0, ends[i - 1] - x) if i else 0.0
    scoped = {}          # program -> phase of its last operation that has a scope path

    def add(key, seconds):
        table[key] = table.get(key, 0.0) + seconds

    for (name, start, dur), program in zip(events, programs):
        s, e = max(start, reach, lo), min(start + dur, hi)
        if e <= s:
            continue
        if program is None:
            key = (UNASSIGNED, "", "")
        else:
            op_name = catalog[program]["ops"].get(instruction(name), "")
            part = PART_RE.search(op_name)
            if op_name or program not in scoped:
                scoped[program] = phase_of(program, op_name, update_programs)
            key = (scoped[program], part.group(0) if part else "", kernel_of(name, op_name))
        covered = busy_until(s) - busy_until(reach) if s > reach else 0.0
        if covered > 0:
            add((key[0], "enclosing", "") if before == key[0] else (UNASSIGNED, "", ""), covered)
        add(key, e - s)
        reach, before = e, key[0]
    return table


# -------------------------------------------------------------------- analyse
def analyse(record):
    """Everything the readers return, worked out once and kept on the record; None
    where there is no recorder or no window."""
    if "program_spans" in record:
        return record["program_spans"]
    record["program_spans"] = result = _analyse(record)
    if result is not None and result["trace"]:
        _leave_table(result)
    return result


def _analyse(record):
    rec = program_recorder()
    t0, window_s = record.get("t_window_start"), record.get("window_s")
    if rec is None or t0 is None or not window_s:
        return None
    spans = rec.spans()
    steps, engine = window_steps(spans, t0, t0 + window_s)
    if not steps:
        return None
    spans = [s for s in spans if s["engine"] == engine]
    table, step_self = host_table(spans, steps)
    counters = rec.counters(engine)
    result = {
        "engine": engine, "steps": len(steps), "spans": table,
        "engine_self_ms_p50": 1e3 * statistics.median(step_self),
        "counters": counters,
        "program_builds": sum(v for k, v in counters.items()
                              if k.startswith("program.builds[")),
        "build_s": build_seconds(spans, engine, t0),
        "trace": None,
    }
    trace = record.get("trace")
    if trace is not None and trace.devices and trace.window_s > 0:
        result["trace"] = _analyse_trace(record, trace, rec, engine, spans, steps)
    return result


def _analyse_trace(record, trace, rec, engine, spans, steps):
    out = {"window_s": trace.window_s}
    returns, t = [], record["t_window_start"]
    for ms in record.get("step_interval_ms", ()):
        t += ms * 1e-3
        returns.append(t)
    offset = clock_offset(trace.host, returns)
    first = next(iter(trace.devices))
    if offset is not None:
        out["clock_offset_s"] = offset
        gaps = tr.gaps(trace.busy[first], trace.lo, trace.hi)
        out["idle_s"] = idle_by_span(gaps, spans, offset)
        out["stall_ms_per_step"] = 1e3 * sum(
            v for k, v in out["idle_s"].items() if k != CALLER) / len(steps)
    try:
        catalog = rec.programs(engine)      # compiles, or loads from the persistent cache
    except Exception as e:                  # a traced run must still print its line
        catalog, out["catalog_error"] = {}, repr(e)
    if catalog:
        update = {s["attrs"].get("program") for s in spans
                  if s["name"] == "train.update_program"}
        tables = [device_table(ev, trace.busy[dev], trace.lo, trace.hi, catalog, update)
                  for dev, ev in trace.devices.items()]
        keys = sorted({k for t in tables for k in t})
        table = {k: sum(t.get(k, 0.0) for t in tables) / len(tables) for k in keys}
        out["device_s"] = [[*k, v] for k, v in table.items()]
        by_phase = {p: sum(v for k, v in table.items() if k[0] == p) for p in PHASES}
        out["phase_s"] = by_phase
        out["kernel_s"] = {}
        for k, v in table.items():
            if k[2]:
                out["kernel_s"][k[2]] = out["kernel_s"].get(k[2], 0.0) + v
        # the device's busy time less what was given to a phase
        out["unassigned_s"] = trace.busy_s() - sum(by_phase.values())
    return out


def _leave_table(result):
    """``benchmarks/out/program_spans.last.json``: the whole table, for PERF.md."""
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")
    os.makedirs(out_dir, exist_ok=True)      # where the harness writes its records too
    with open(os.path.join(out_dir, OUT_NAME), "w") as f:
        json.dump(result, f, indent=1)


# -------------------------------------------------------------------- readers
def host_value(record, key):
    result = analyse(record)
    return None if result is None else result.get(key)


def trace_value(record, key):
    result = analyse(record)
    if result is None or not result.get("trace"):
        return None
    return result["trace"].get(key)


def phase_share(record, phase):
    """Device seconds of ``phase`` over the traced window, in percent; None where more
    than ``MAX_UNASSIGNED`` of the window could be given to no phase."""
    by_phase, window_s = trace_value(record, "phase_s"), trace_value(record, "window_s")
    if not by_phase:
        return None
    if trace_value(record, "unassigned_s") > MAX_UNASSIGNED * window_s:
        return None
    return 100.0 * by_phase[phase] / window_s


def flash_roofline(record, kernels, forward):
    """The least time the chip could take for the window's calls of ``kernels`` (the
    forward's requirement, or the training step's less the forward's) over their time
    in the trace, in percent."""
    from benchmarks import flops, peaks
    seconds = sum((trace_value(record, "kernel_s") or {}).get(k, 0.0) for k in kernels)
    if seconds <= 0 or record.get("kind") != "train":
        return None
    steps = trace_value(record, "window_s") * record["tokens_per_s_chip"] / (
        record["batch_per_chip"] * record["seq_len"])
    args = (record["model"], record["batch_per_chip"], record["seq_len"])
    fwd_flops, fwd_bytes = flops.flash_required(*args, training=False)
    if forward:
        need_flops, need_bytes = fwd_flops, fwd_bytes
    else:
        all_flops, all_bytes = flops.flash_required(*args, training=True)
        need_flops, need_bytes = all_flops - fwd_flops, all_bytes - fwd_bytes
    least, _ = flops.roofline_seconds(need_flops * steps, need_bytes * steps,
                                      peaks.peaks_for(record["device_kind"]))
    return 100.0 * least / seconds
