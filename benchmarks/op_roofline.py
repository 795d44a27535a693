"""Every compiled operation of a traced window against what it HAS to do.

The program prices its own instructions (``recorder.programs()[program]["cost"]``:
``[flops, bytes]`` an instruction, the products' operations and a floor of the HBM bytes,
``deepspeed_tpu/utils/hlo.instruction_costs``); the trace says how long each took. On
``program_spans.analyse(record)``'s base (its engine, the catalog, ``assign_programs``,
``phase_of``, the window) every innermost device operation is joined by instruction name to
its program's cost and falls into one class:

    kernel      a ``tpu_custom_call``: it has a metric of its own against the algorithm's need
    collective  the links' work: by the trace's name, or because the program's ``collectives`` says so
    product     ``flops`` > 0: a fusion around a matmul; the larger of flops / peak and bytes / HBM rate
    memory      ``flops`` = 0: elementwise, reductions, gathers, copies; bytes / HBM rate, and a
                wait for an asynchronous copy at a floor of nothing
    unpriced    in no program, or an instruction the pricing leaves out

and its floor is ``flops.roofline_seconds(flops, bytes, peaks)``. Left in
``benchmarks/out/op_roofline.last.json``: the classes' device and floor seconds by side (the
gradient program's operations, the update's), and THE ROWS, named as ``trace_reduce.op_group``
names a ledger row (a tuple-valued fusion by its product's element), each with phase, part,
the product's ``[M, K, N]``, what bounds it, floor and measured ms a step, and ms a step over
the floor, by which they are sorted: where a ``perf_opt`` issue starts. Means over the devices.
``unseen_s`` is the busy time that no innermost operation covers: a ``while`` between two of its
body's operations, and an operation ``trace_reduce.leaves`` took for an enclosing one because a
zero-length ``copy-start`` fell inside it; such an operation is in no row and no class.

    python benchmarks/op_roofline.py [benchmarks/out/op_roofline.last.json] [--top 20]

prints the table's head. Against a program without ``cost`` (the parent of the PR that added
it), or a record with no trace, everything here returns None and raises nothing.
"""

import json
import os
import re
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import flops, peaks, program_spans
from benchmarks import trace_reduce as tr

KERNEL, COLLECTIVE, PRODUCT, MEMORY, UNPRICED = "kernel", "collective", "product", "memory", "unpriced"
PRICED = (PRODUCT, MEMORY)
GRADIENT, UPDATE = "gradient", "update"
RECOMPUTED = "rematted_computation"
SCOPE_RE = re.compile(r"\bds_(?!fwd_bwd\b)\w+")
OUT_NAME = "op_roofline.last.json"


def class_of(name, cost, collective=False):
    """The class of one device operation: ``name`` as the trace prints it, ``cost`` its
    instruction's ``[flops, bytes]`` or None, ``collective`` whether its program says it is
    one (a reduce-scatter the compiler wrote as a fusion has a fusion's name)."""
    if "tpu_custom_call" in name:
        return KERNEL
    if collective or tr.is_collective(name):
        return COLLECTIVE
    if cost is None:
        return UNPRICED
    return PRODUCT if cost[0] > 0 else MEMORY


def row_name(name, product):
    """The ledger's name for the operation's row; a tuple-valued fusion, which a trace names
    by its first element (a reduction that rides along), by the element its product fills."""
    group = tr.op_group(name)
    if product and product.get("as"):
        return f"{group.split(' ', 1)[0]} {product['as']}"
    return group


def table(reduced, catalog, update_programs, peak, steps):
    """The classes and the rows of one reduced trace (``trace_reduce.Reduced``) under one
    catalog; ``steps`` is what "a step" divides by. None where a program has no ``cost``."""
    if not catalog or any("cost" not in info for info in catalog.values()):
        return None
    sides = {side: {c: {"device_s": 0.0, "floor_s": 0.0, "events": 0.0}
                    for c in (KERNEL, COLLECTIVE, PRODUCT, MEMORY, UNPRICED)}
             for side in (GRADIENT, UPDATE)}
    rows, n, unseen_s = {}, len(reduced.devices), 0.0
    collectives = {program: set(info.get("collectives", ())) for program, info in catalog.items()}
    for device, events in reduced.devices.items():
        events = sorted(events, key=lambda e: e[1])
        seen = tr.union(tr.clip([[s, s + d] for _, s, d in events], reduced.lo, reduced.hi))
        unseen_s += (tr.measure(reduced.busy[device]) - tr.measure(seen)) / n
        scoped = {}                   # program -> (phase, part) of its last scoped operation
        for (name, start, dur), program in zip(events,
                                               program_spans.assign_programs(events, catalog)):
            seconds = tr.measure(tr.clip([[start, start + dur]], reduced.lo, reduced.hi))
            if seconds <= 0:
                continue
            cost = product = None
            phase, part, collective = "", "", False
            if program is not None:
                info, instruction = catalog[program], program_spans.instruction(name)
                cost = info["cost"].get(instruction)
                product = info.get("products", {}).get(instruction)
                collective = instruction in collectives[program]
                path = info["ops"].get(instruction, "")
                if path or program not in scoped:
                    found = SCOPE_RE.findall(path)
                    phase = program_spans.phase_of(program, path, update_programs)
                    if phase == "backward" and RECOMPUTED in path:
                        phase = "recompute"
                    scoped[program] = (phase, found[-1] if found else "")
                phase, part = scoped[program]
            kind = class_of(name, cost, collective)
            floor = 0.0
            if kind in PRICED:
                floor = flops.roofline_seconds(cost[0], cost[1], peak)[0]
                floor *= seconds / dur            # an operation cut by the window's edge
            side = sides[UPDATE if phase == "optimizer" else GRADIENT][kind]
            side["device_s"] += seconds / n
            side["floor_s"] += floor / n
            side["events"] += 1.0 / n
            if kind not in PRICED:
                continue
            mkn = max(product["mkn"], key=lambda p: p[0] * p[1] * p[2]) if product else None
            key = (row_name(name, product), phase, part, tuple(mkn) if mkn else None)
            row = rows.setdefault(key, {"device_s": 0.0, "floor_s": 0.0, "events": 0.0,
                                        "flops": 0.0, "bytes": 0.0, "class": kind})
            row["device_s"] += seconds / n
            row["floor_s"] += floor / n
            row["events"] += 1.0 / n
            row["flops"] += cost[0] / n
            row["bytes"] += cost[1] / n
    per_step = 1e3 / steps
    listed = []
    for (name, phase, part, mkn), row in rows.items():
        measured, floor = row["device_s"] * per_step, row["floor_s"] * per_step
        listed.append({"name": name, "class": row["class"], "phase": phase, "part": part,
                       "mkn": list(mkn[:3]) if mkn else None, "types": mkn[3] if mkn else None,
                       "events_a_step": row["events"] / steps,
                       "flops_a_step": row["flops"] / steps, "bytes_a_step": row["bytes"] / steps,
                       "bound": (flops.roofline_seconds(row["flops"], row["bytes"], peak)[1]
                                 if row["floor_s"] > 0 else ""),
                       "floor_ms": floor, "measured_ms": measured,
                       "share": 100.0 * floor / measured if measured > 0 else 0.0,
                       "over_floor_ms": measured - floor})
    listed.sort(key=lambda r: -r["over_floor_ms"])
    return {"window_s": reduced.window_s, "steps": steps,
            "peaks": {k: peak[k] for k in ("flops_per_s", "hbm_bytes_per_s")},
            "sides": sides, "unseen_s": unseen_s,
            "product_flops_a_step": sum(r["flops_a_step"] for r in listed),
            "worst_share": max((r["share"] for r in listed), default=0.0),
            "rows": listed}


# -------------------------------------------------------------------- analyse
def analyse(record):
    """``table`` of the record's traced window, worked out once and kept on the record;
    None without a trace, a recorder, a catalog or ``cost`` in it."""
    if "op_roofline" in record:
        return record["op_roofline"]
    record["op_roofline"] = result = _analyse(record)
    if result is not None:
        out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, OUT_NAME), "w") as f:
            json.dump(result, f, indent=1)
    return result


def _analyse(record):
    trace = record.get("trace")
    base = program_spans.analyse(record)
    if trace is None or base is None or not trace.devices or trace.window_s <= 0:
        return None
    rec = program_spans.program_recorder()
    try:
        catalog = rec.programs(base["engine"])
    except Exception:          # the catalog compiles; a traced run must still print its line
        return None
    update = {s["attrs"].get("program") for s in rec.spans()
              if s["engine"] == base["engine"] and s["name"] == "train.update_program"}
    return table(trace, catalog, update, peaks.peaks_for(record["device_kind"]), base["steps"])


# -------------------------------------------------------------------- readers
def side_share(record, side, classes, of_window=False):
    """Percent: the floor seconds of ``classes`` on ``side`` over their device seconds, or
    (``of_window``) their device seconds over the window. None where there is nothing to
    read, and where the side's unpriced time passes ``program_spans.MAX_UNASSIGNED`` of the
    window: a share over a part of the work is not given out as the whole's."""
    result = analyse(record)
    if result is None:
        return None
    mine = result["sides"][side]
    if mine[UNPRICED]["device_s"] > program_spans.MAX_UNASSIGNED * result["window_s"]:
        return None
    device_s = sum(mine[c]["device_s"] for c in classes)
    if device_s <= 0:
        return None
    if of_window:
        return 100.0 * device_s / result["window_s"]
    return 100.0 * sum(mine[c]["floor_s"] for c in classes) / device_s


def main(argv):
    path = next((a for a in argv if not a.startswith("--")),
                os.path.join(os.path.dirname(os.path.abspath(__file__)), "out", OUT_NAME))
    top = int(argv[argv.index("--top") + 1]) if "--top" in argv else 20
    with open(path) as f:
        result = json.load(f)
    print(f"{'over ms':>8} {'ms':>8} {'floor':>8} {'share':>6}  bound    phase      part, row")
    for r in result["rows"][:top]:
        shape = f" {r['mkn']} {r['types']}" if r["mkn"] else ""
        print(f"{r['over_floor_ms']:8.3f} {r['measured_ms']:8.3f} {r['floor_ms']:8.3f} "
              f"{r['share']:5.1f}%  {r['bound']:8} {r['phase']:10} {r['part']}  {r['name']}{shape}")


if __name__ == "__main__":
    main(sys.argv[1:])
