"""How far the host runs ahead of the device, and what holds it there.

The program's recorder (``deepspeed_tpu/utils/spans.py``) gives every span the CPU seconds
of its thread beside its wall seconds, ``train.step`` the steps still in flight when it
began and the device memory in use when its programs were enqueued, and the catalog each
step program's memory as the compiler states it. The reduction is the benchmark's and lives
here, worked out once a record like ``program_spans.analyse``; the window's steps, the clock
offset, the catalog and the assignment of device operations to programs are that module's.

    host side   per span name a step: wall, CPU and held (wall less CPU) milliseconds, the
                median and the mean (where the machine's thread clock ticks, 10 ms on the
                benchmark's hosts, only the mean is finer than a tick); steps in flight;
                bytes in use against the limit; the shortest step's wall time (the window opens
                on a drained device, so its first step is held by nothing: the host's own cost,
                finer than the tick); every stalled step with the part that took the excess and
                whose it was
    device      every call of a step program paired with its execution on the first device,
                from the window's END backwards, program by program: the fence guarantees
                that the last call ran, and the window's first executions may have been
                launched before it opened. A run of one program's operations is one
                execution. The lead of a call is the moment the device was free for it (the
                end of the execution before it; the window's start where nothing ran before)
                less the end of the span that launched it: positive, the program waited in
                the device's queue; negative, the device waited for the host, by that long

Against a program whose spans carry none of this (the parent of the PR that added it) each
number is None; the pairing needs only what the parent has. Nothing here raises on a record
with no window, no recorder or no trace.
"""

import itertools
import json
import os
import statistics

from benchmarks import harness
from benchmarks import program_spans as ps
from benchmarks import trace_reduce as tr

OUT_NAME = "host_lead.last.json"
STALLED = 1.5              # a step this many median steps long is listed, if it and the next one
PAIR_STALLED = 2.25        # together took this many: a host two steps ahead is held two steps' time
                           # in one call and none in the next, which is its pace and no stall
SELF = "train.step.self"   # the step less its children: the engine's Python, and the caller's
MAX_IN_FLIGHT = 8          # the engine looks back eight steps: more executions than that ahead of
                           # a window's first call are no lead, the pairing has gone wrong
CAUSAL_SLACK_S = 1e-3      # a device cannot start a program before the host called it


# ----------------------------------------------------------------- host side
def step_parts(step, kids, period):
    """{part: (wall s, cpu s or None, is a program call)} of one step that took ``period``
    from its start to the next one's: its children by name, summed; ``SELF``; and the
    caller's time between the step's end and the next step."""
    parts, own = {}, kids.get(step["id"], [])
    for c in own:
        wall, cpu, call = parts.get(c["name"], (0.0, 0.0, False))
        cpu = None if cpu is None or c.get("cpu_s") is None else cpu + c["cpu_s"]
        parts[c["name"]] = (wall + c["end"] - c["start"], cpu, call or "program" in c["attrs"])
    cpu = step.get("cpu_s")          # a ``compile.*`` child has none: its CPU stays the step's
    parts[SELF] = (ps.self_seconds(step, own),
                   None if cpu is None else cpu - sum(c.get("cpu_s") or 0.0 for c in own), False)
    parts[ps.CALLER] = (max(0.0, period - (step["end"] - step["start"])), None, False)
    return parts


def whose(name, excess_s, excess_cpu_s, program_call):
    """A stall with CPU under it is Python's (a collection); one held inside a program
    call is the runtime's; one with neither is the machine's."""
    if name == ps.CALLER:
        return ps.CALLER
    if excess_cpu_s is None:
        return None
    if excess_cpu_s >= 0.5 * excess_s:
        return "python"
    return "runtime" if program_call else "machine"


def host_side(spans, steps):
    kids = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)
    # a step's length: from its start to the next one's, so that the caller's share counts
    periods = [b["start"] - a["start"] for a, b in zip(steps, steps[1:])]
    periods.append(steps[-1]["end"] - steps[-1]["start"])
    parts = [step_parts(s, kids, period) for s, period in zip(steps, periods)]
    nothing = (0.0, 0.0, False)
    table, usual = {}, {}
    for name in sorted({n for p in parts for n in p}):
        wall = [p.get(name, nothing)[0] for p in parts]
        cpu = [p.get(name, nothing)[1] for p in parts]
        known = None not in cpu
        usual[name] = (statistics.median(wall), statistics.median(cpu) if known else None)
        table[name] = {
            "wall_ms": 1e3 * usual[name][0], "wall_ms_mean": 1e3 * statistics.mean(wall),
            "cpu_ms": 1e3 * usual[name][1] if known else None,
            "cpu_ms_mean": 1e3 * statistics.mean(cpu) if known else None,
            "held_ms": 1e3 * statistics.median(w - c for w, c in zip(wall, cpu)) if known else None,
            "held_ms_mean": 1e3 * (statistics.mean(wall) - statistics.mean(cpu)) if known else None}
    step_cpu = [s.get("cpu_s") for s in steps]
    flights = [s["attrs"].get("in_flight") for s in steps]
    # the device's pace: two successive steps' time, halved (``harness.step_profile``'s median)
    pace = statistics.median([(a + b) / 2 for a, b in zip(periods, periods[1:])] or periods)
    stalled = []
    for i, (step, mine, period, flight) in enumerate(zip(steps, parts, periods, flights)):
        after = periods[i + 1] if i + 1 < len(periods) else pace
        if period <= STALLED * pace or period + after <= PAIR_STALLED * pace:
            continue
        name = max(mine, key=lambda n: mine[n][0] - usual[n][0])
        (wall, cpu, call), (usual_wall, usual_cpu) = mine[name], usual[name]
        stalled.append({
            "step": step["step"], "at_s": step["start"], "ms": 1e3 * period,
            "with_the_next_ms": 1e3 * (period + after),
            "span": name, "span_ms": 1e3 * wall, "span_median_ms": 1e3 * usual_wall,
            "span_cpu_ms": None if cpu is None else 1e3 * cpu,
            "span_held_ms": None if cpu is None else 1e3 * (wall - cpu),
            "in_flight": flight,
            "whose": whose(name, wall - usual_wall,
                           None if cpu is None or usual_cpu is None else cpu - usual_cpu, call)})
    return {
        "spans": table,
        "engine_cpu_ms_p50": None if None in step_cpu else 1e3 * statistics.median(step_cpu),
        "engine_cpu_ms_mean": None if None in step_cpu else 1e3 * statistics.mean(step_cpu),
        "in_flight": None if None in flights else {
            "min": min(flights), "median": statistics.median(flights), "max": max(flights)},
        "step_ms": 1e3 * pace, "stalled_steps": stalled,
        # a step that nothing held (the window's first, after the fence) is the host's own cost
        "step_wall_ms_min": 1e3 * min(s["end"] - s["start"] for s in steps),
    }


def memory(spans, steps):
    """The window's ``bytes_in_use`` against the ``bytes_limit`` the engine's first step
    noted; None where the program notes none (the CPU, or the parent)."""
    used = [s["attrs"]["bytes_in_use"] for s in steps if "bytes_in_use" in s["attrs"]]
    limit = next((s["attrs"]["bytes_limit"] for s in spans if "bytes_limit" in s["attrs"]), None)
    if not used:
        return None
    return {"bytes_in_use_min": min(used), "bytes_in_use_max": max(used), "bytes_limit": limit,
            "share_max": 100.0 * max(used) / limit if limit else None}


# --------------------------------------------------------------- device side
def executions(events, catalog):
    """``[[program, start, end]]`` on one device, in time order: a run of one program's
    operations is one execution (an operation no program claims alone goes with neither
    side). Two executions of one program with nothing between them read as one, and the
    counts then do not pair."""
    events = sorted(events, key=lambda e: e[1])
    runs = []
    for (_, start, dur), program in zip(events, ps.assign_programs(events, catalog)):
        if program is None:
            continue
        if runs and runs[-1][0] == program:
            runs[-1][2] = max(runs[-1][2], start + dur)
        else:
            runs.append([program, start, start + dur])
    return runs


def pair_calls(calls, runs, lo, hi):
    """``[(call, free, start)]`` or None. ``calls`` are the launching spans on the trace's
    clock; ``runs`` are ``executions``. Paired program by program from the end; an
    execution's ``free`` is the end of the run before it, whatever its program; nothing ran
    before the trace's first, and the device was free for it since the window opened."""
    pairs = []
    for program in sorted({c["attrs"]["program"] for c in calls}):
        mine = sorted((c for c in calls if c["attrs"]["program"] == program),
                      key=lambda c: c["end"])
        ran = [i for i, r in enumerate(runs) if r[0] == program and lo <= r[1] <= hi]
        if not 0 <= len(ran) - len(mine) <= MAX_IN_FLIGHT:
            return None
        for call, i in zip(mine, ran[len(ran) - len(mine):]):
            start = runs[i][1]
            if start < call["start"] - CAUSAL_SLACK_S:
                return None
            pairs.append((call, runs[i - 1][2] if i else lo, start))
    return pairs or None


def distribution(values):
    values = sorted(values)
    return {"count": len(values), "min": values[0], "p10": harness.percentile(values, 10),
            "p50": harness.percentile(values, 50), "p90": harness.percentile(values, 90),
            "max": values[-1]}


def lead_table(pairs):
    """The leads of ``pair_calls``' pairs, in milliseconds: all of them and by program."""
    by_program = {}
    for call, free, start in pairs:
        row = by_program.setdefault(call["attrs"]["program"], {"lead": [], "start": [], "gap": []})
        row["lead"].append(1e3 * (free - call["end"]))
        row["start"].append(1e3 * (start - call["end"]))
        row["gap"].append(1e3 * (start - free))
    leads = [v for row in by_program.values() for v in row["lead"]]
    return {
        "launch_lead_ms_p10": harness.percentile(leads, 10),
        "negative_lead_s": 1e-3 * sum(-v for v in leads if v < 0),
        "by_program": {p: {"lead_ms": distribution(row["lead"]),
                           "start_less_return_ms": distribution(row["start"]),
                           "idle_before_ms": distribution(row["gap"]),
                           "leads_ms": [round(v, 3) for v in row["lead"]]}
                       for p, row in by_program.items()},
    }


def offset_spread(host, returns, offset):
    """How far a step's own difference of the two clocks lies from the offset, in
    microseconds: over the window, and the medians of its two halves (a drift shows as
    two halves apart)."""
    ends = sorted(e for name, _, e in host if name == "dispatch")
    off = [1e6 * (e - r - offset) for e, r in zip(ends, returns)]
    half = len(off) // 2
    return {"min_us": min(off), "max_us": max(off),
            "first_half_median_us": statistics.median(off[:half] or off),
            "second_half_median_us": statistics.median(off[half:])}


def device_side(record, trace, catalog, spans, stalled):
    base = ps.analyse(record)["trace"] or {}
    offset = base.get("clock_offset_s")
    if offset is None:
        return None
    out = {"clock_offset_s": offset}
    returns = itertools.accumulate((ms * 1e-3 for ms in record.get("step_interval_ms", ())),
                                   initial=record["t_window_start"])
    out["clock_offset_spread"] = offset_spread(trace.host, list(returns)[1:], offset)
    first = next(iter(trace.devices))
    gaps = tr.gaps(trace.busy[first], trace.lo, trace.hi)
    for row in stalled:          # the device's idle time under a stalled step
        a = row["at_s"] + offset
        row["device_idle_ms"] = 1e3 * tr.measure(tr.clip(gaps, a, a + row["ms"] * 1e-3))
    idle = base.get("idle_s") or {}
    calls = [dict(s, start=s["start"] + offset, end=s["end"] + offset) for s in spans
             if s["attrs"].get("program") in catalog
             and trace.lo <= s["end"] + offset <= trace.hi]
    out["idle_in_program_calls_s"] = sum(idle.get(n, 0.0) for n in {c["name"] for c in calls})
    pairs = pair_calls(calls, executions(trace.devices[first], catalog), trace.lo, trace.hi)
    if pairs is not None:
        out.update(lead_table(pairs))
    return out


# -------------------------------------------------------------------- analyse
def analyse(record):
    """Everything the readers return, worked out once and kept on the record; None where
    there is no recorder or no window."""
    if "host_lead" in record:
        return record["host_lead"]
    record["host_lead"] = result = _analyse(record)
    if result is not None and result["trace"]:
        _leave_table(result)
    return result


def _leave_table(result):
    """``benchmarks/out/host_lead.last.json``: the whole table, for PERF.md."""
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, OUT_NAME), "w") as f:
        json.dump(result, f, indent=1)


def _analyse(record):
    rec = ps.program_recorder()
    base = ps.analyse(record)
    if rec is None or base is None:
        return None
    spans = [s for s in rec.spans() if s["engine"] == base["engine"]]
    t0 = record["t_window_start"]
    steps, _ = ps.window_steps(spans, t0, t0 + record["window_s"])
    result = {"engine": base["engine"], "steps": len(steps)}
    result.update(host_side(spans, steps))
    result["memory"] = memory(spans, steps)
    result["trace"] = result["program_memory"] = None
    trace = record.get("trace")
    if trace is not None and trace.devices and trace.window_s > 0:
        try:
            catalog = rec.programs(base["engine"])   # kept since ``program_spans`` asked
        except Exception:            # it compiles; a traced run must still print its line
            catalog = {}
        result["program_memory"] = {p: info["memory"] for p, info in catalog.items()
                                    if info.get("memory")} or None
        result["trace"] = device_side(record, trace, catalog, spans, result["stalled_steps"])
    return result


# -------------------------------------------------------------------- readers
def host_value(record, key):
    result = analyse(record)
    return None if result is None else result.get(key)


def trace_value(record, key):
    result = analyse(record)
    if result is None or not result.get("trace"):
        return None
    return result["trace"].get(key)
