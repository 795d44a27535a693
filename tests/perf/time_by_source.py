"""A cell's device time by the SOURCE LINES its operations were traced from, for work that
carries no scope of its own (a backward JAX writes, a plain ``jnp`` form inside a mixer).

    chiprun --timeout 1800 -- python tests/perf/time_by_source.py --workload granite4h_d10_train_1chip \
        --seed 3600000101 --out /root/repo/chiprun_out/by_source/granite

One traced run of the cell through ``benchmarks/run.py``'s own ``run_cell`` (its result line is
printed as the command prints it, and ``program_spans.last.json`` is left as always), with
the profiler's files kept. Then every operation of the first device inside the window gets
its instruction of the OPTIMIZED step program (``recorder.programs()`` compiles it; the text
is caught on its way through) and, from the compiler's metadata, the scope path of the
instruction and the ``file:line`` of every instruction fused into it. Left under ``--out``:

    <out>.ops.json      every instruction that ran: ms a step, calls a step, what the trace
                        calls it, its scope path, {file:line: instructions fused from it}
                        and {scope path: instructions fused from it}
    <out>.<module>.hlo.txt.gz   the optimized text, a kernel's serialized body cut out
    <out>.result.json   the run's result line

and printed: the operations in which at least half of what was fused comes from a line
(``file.py:NN``, the innermost frame) or a scope path that the regular expression ``--match``
finds (the convolution's scope ``ds_conv`` unless given), by forward, second forward
(``rematted_computation``) and backward (``transpose(``). A fusion has one time, whatever it
was fused from: ``any`` sums those that hold at least one such instruction, an upper bound.
From the root of a parent unpacked under ``_parent/`` it measures that tree (give ``--out`` an
absolute path there).
"""

import argparse
import collections
import gzip
import json
import os
import re
import sys

sys.path.insert(0, ".")

DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
HEAD_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
FRAME_RE = re.compile(r"stack_frame_id=(\d+)")
OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
# what ``--match`` finds unless given: the convolution's scope. PR 36 read the PARENT's plain
# form, which had no scope, by its lines and call sites (where JAX puts the backward of a
# ``jax.checkpoint``ed function): r"delta_rule\.py:(3\d|4[0-4])$|granite_hybrid\.py:172$|qwen3_next\.py:165$"
CONVOLUTION = r"\bds_conv\b"


def frames(text):
    """``{stack_frame_id: file:line}`` (the innermost frame's) from the tables a module's text
    opens with: ``FileNames``, ``FileLocations``, ``StackFrames``."""
    tables, table = collections.defaultdict(dict), None
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            table = line
        elif table and re.match(r"\d+ ", line):
            key, _, rest = line.partition(" ")
            tables[table][int(key)] = rest
        elif line.startswith(("%", "ENTRY")):
            break
    files = {k: os.path.basename(v.strip('"')) for k, v in tables["FileNames"].items()}
    places = {}
    for k, v in tables["FileLocations"].items():
        got = dict(re.findall(r"(\w+)=(\d+)", v))
        places[k] = f"{files.get(int(got['file_name_id']), '?')}:{got['line']}"
    return {k: places.get(int(re.search(r"file_location_id=(\d+)", v).group(1)), "")
            for k, v in tables["StackFrames"].items()}


def instructions(text):
    """``{instruction: (scope path, {file:line: count}, {scope path: count})}`` over every
    computation: an instruction's own line (the innermost frame of its traceback), and for a
    fusion the lines and the scope paths of what was fused into it. (What a ``jax.checkpoint``
    traces again keeps its scope path and loses its line, but for the calls of jitted
    functions inside it, a ``pad``, a ``silu``: the paths tell where the lines do not.)"""
    own, inside, called, comp = {}, collections.defaultdict(collections.Counter), {}, None
    paths = collections.defaultdict(collections.Counter)
    frame_places = frames(text)
    for line in text.splitlines():
        head = HEAD_RE.match(line) if " = " not in line.split("{")[0] else None
        if head:
            comp = head.group(1)
            continue
        d = DEF_RE.match(line)
        if not d:
            continue
        frame = FRAME_RE.search(line)
        place = frame_places.get(int(frame.group(1)), "") if frame else ""
        op_name = OP_NAME_RE.search(line)
        own[d.group(1)] = (op_name.group(1) if op_name else "", place)
        if place:
            inside[comp][place] += 1
        if op_name:
            paths[comp][op_name.group(1)] += 1
        calls = CALLS_RE.search(line)
        if calls and " fusion(" in line:
            called[d.group(1)] = calls.group(1)
    out = {}
    for name, (op_name, place) in own.items():
        lines = dict(inside[called[name]]) if name in called else ({place: 1} if place else {})
        out[name] = (op_name, lines, dict(paths[called[name]]) if name in called else {})
    return out


def cut(text, longest=4000, keep=1500):
    """The text with a kernel's serialized body taken out of its line."""
    return "\n".join(line if len(line) <= longest else line[:keep] + " ...CUT... " + line[-keep:]
                     for line in text.splitlines())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--match", default=CONVOLUTION)
    ap.add_argument("--out", required=True)
    opts = ap.parse_args()
    os.makedirs(os.path.dirname(os.path.abspath(opts.out)), exist_ok=True)

    from benchmarks import program_spans, run, trace_reduce
    from deepspeed_tpu.utils import hlo

    texts, module_name = {}, hlo.module_name

    def caught(text):
        texts[module_name(text)] = text
        return module_name(text)
    hlo.module_name = caught          # ``Programs.catalog`` hands every compiled text through it

    out_dir = os.path.join(run.BENCH_DIR, "out")
    result = run.run_cell(opts.workload, opts.seed, 40.0, True, keep_trace=True, out_dir=out_dir)
    print(json.dumps(result), flush=True)
    with open(opts.out + ".result.json", "w") as f:
        json.dump(result, f)

    rec = program_spans.program_recorder()
    catalog = rec.programs(max(rec._programs))          # the cell's engine is the process's last
    known = {}
    for module, text in texts.items():
        known[module] = instructions(text)
        with gzip.open(f"{opts.out}.{module}.hlo.txt.gz", "wt") as f:
            f.write(cut(text))
    path = trace_reduce.find_xplane(os.path.join(out_dir, f"trace.{opts.workload}.{opts.seed}"))
    reduced = trace_reduce.Reduced(trace_reduce.load_xplane(path))
    steps = result["attempted"]
    events = sorted(reduced.devices[next(iter(reduced.devices))], key=lambda e: e[1])
    ops = {}
    for (name, start, dur), program in zip(events, program_spans.assign_programs(events, catalog)):
        got = trace_reduce.measure(trace_reduce.clip([[start, start + dur]], reduced.lo, reduced.hi))
        if got <= 0:
            continue
        key = program_spans.instruction(name)
        module = catalog[program]["module"] if program else ""
        op_name, lines, fused = known.get(module, {}).get(key, ("", {}, {}))
        row = ops.setdefault(f"{program}:{key}", dict(trace_name=name, ms_a_step=0.0, calls_a_step=0.0,
                                                      op_name=op_name, lines=lines, fused=fused))
        row["ms_a_step"] += 1e3 * got / steps
        row["calls_a_step"] += 1.0 / steps
    with open(opts.out + ".ops.json", "w") as f:
        json.dump(dict(steps=steps, window_s=reduced.window_s, ops=ops), f)

    half, any_, match = collections.Counter(), collections.Counter(), re.compile(opts.match)
    for key, row in ops.items():
        found = {**row["lines"], **row["fused"]}
        mine = sum(n for place, n in found.items() if match.search(place))
        own = bool(match.search(row["op_name"]))
        if not mine and not own:
            continue
        # what a checkpoint makes again runs inside the backward: its path holds both names
        phase = ("second_forward" if "rematted_computation" in row["op_name"] else
                 "backward" if "transpose(" in row["op_name"] else "forward")
        any_[phase] += row["ms_a_step"]
        if own or 2 * mine >= sum(found.values()):
            half[phase] += row["ms_a_step"]
    print(json.dumps(dict(match=opts.match, steps=steps, ms_a_step_at_least_half=dict(half),
                          ms_a_step_any=dict(any_), total_half=sum(half.values()),
                          total_any=sum(any_.values()))), flush=True)


if __name__ == "__main__":
    main()
