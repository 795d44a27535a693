"""Optimized-HLO inspection: collectives, aliasing, entry layout.

The framework's multi-chip claims are of the form "XLA emits the collective the
reference called NCCL/MPI for" (zero/sharding.py, pipeline_spmd.py, ring_attention.py,
custom_collectives.py). This module is the shared audit surface for that claim: it
parses a compiled program's text for collective instructions so tests
(tests/unit/test_collectives_hlo.py), the driver dry-run (__graft_entry__.py), the
program lint passes (deepspeed_tpu/lint/program_passes.py) and users debugging
shardings can count them and account wire bytes from ONE parser. The lint suite
additionally needs the module-header facts — ``input_output_alias`` (which donations
XLA actually honored) and ``entry_computation_layout`` (parameter/result types) —
parsed here for the same single-parser reason.
"""

import re
from collections import Counter

import numpy as np

COLLECTIVE_OPS = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
                  "collective-permute")

# `%name = TYPE op(...)` where TYPE is a shaped type or a tuple of them
# (all-to-all returns a tuple). The optional ``-start`` suffix folds the async
# variants into their base op: ``all-gather-start`` IS the program's all-gather
# (the paired ``-done`` carries no transfer of its own and is never matched —
# counting both would double-book the wire).
_OP_RE = re.compile(r"= (\([^)]*\)|\S+) (" + "|".join(COLLECTIVE_OPS) +
                    r")(-start)?\(")

_DTYPE_BYTES = {"s4": 1, "u4": 1, "s8": 1, "u8": 1, "pred": 1,
                "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3b11fnz": 1, "f8e4m3fnuz": 1,
                "f8e5m2": 1, "f8e5m2fnuz": 1, "f8e3m4": 1,
                "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

_SHAPED_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def dtype_bytes(dt):
    """Bytes per element of an HLO element-type string, or None if unknown."""
    return _DTYPE_BYTES.get(dt)


def _shaped_types(type_str):
    """[(dtype, (dims...))] for every shaped type inside ``type_str`` (tuples
    flattened; scalars yield empty dims)."""
    out = []
    for dt, dims in _SHAPED_RE.findall(type_str):
        out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def _elements(dims):
    n = 1
    for d in dims:
        n *= d
    return n


# one HLO instruction per `name = type op(...)` line (ROOT-prefixed or not);
# computation headers / ENTRY lines carry no ` = ` and don't match
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.-]+ = ", re.M)


def instruction_count(hlo_text):
    """Total HLO instructions across all computations of the optimized program.
    The telemetry HLO-identity guarantee is stated in these terms: default-mode
    telemetry (named_scope metadata + AOT watchdog) must not change this count."""
    return len(_INSTR_RE.findall(hlo_text))


_METADATA_RE = re.compile(r",?\s*metadata=\{[^{}]*\}")


def instructions(hlo_text):
    """Every instruction of the optimized program as written (name, shape, opcode,
    operands, attributes), less its ``metadata={...}``: what "instruction-identical"
    compares. The module's header tables (FileNames, FileLocations, StackFrames) and
    the metadata's stack frame ids name the CALLER's source lines, so two engines
    lowered from two lines of one file differ there and in nothing that runs."""
    return [_METADATA_RE.sub("", line.strip()) for line in hlo_text.splitlines()
            if _INSTR_RE.match(line)]


def optimized_hlo(jitted, *args):
    """Optimized (post-SPMD-partitioner) HLO text of ``jitted`` on ``args``."""
    return jitted.lower(*args).compile().as_text()


def _collective_matches(hlo_text):
    """(result_type, base_op, is_start) per collective instruction."""
    return [(ty, op, bool(start)) for ty, op, start in _OP_RE.findall(hlo_text)]


def collective_counts(hlo_text):
    """{collective op name -> instruction count} over the optimized HLO.
    Async ``-start`` variants count under their base op name."""
    counts = Counter()
    for _result_ty, op, _start in _collective_matches(hlo_text):
        counts[op] += 1
    return dict(counts)


def _result_shapes(result_ty, op, is_start):
    """Shaped result types of one collective, skipping the bookkeeping an async
    ``-start`` carries. ``all-gather-start`` / ``collective-permute-start``
    return ``(operands..., results...[, u32 context scalars])`` — only the
    produced half is the transfer; ``all-reduce-start`` (and any untupled
    start) returns its results directly."""
    shaped = _shaped_types(result_ty)
    if (is_start and result_ty.startswith("(") and len(shaped) > 1
            and op in ("all-gather", "collective-permute")):
        shaped = [s for s in shaped
                  if not (s[1] == () and s[0] in ("u32", "s32"))]
        return shaped[len(shaped) // 2:]
    return shaped


def collective_results(hlo_text, op=None):
    """[(op, dtype, dims tuple)] of every collective instruction's produced
    results (tuples flattened, async operand echoes skipped). ``op`` filters to
    one base op name."""
    out = []
    for result_ty, found, is_start in _collective_matches(hlo_text):
        if op is not None and found != op:
            continue
        for dt, dims in _result_shapes(result_ty, found, is_start):
            out.append((found, dt, dims))
    return out


def collective_result_types(hlo_text, op):
    """Element-type strings of every ``op`` instruction's results (tuples
    flattened; async ``-start`` variants report their produced buffers only)."""
    return [dt for _op, dt, _dims in collective_results(hlo_text, op)]


def collective_bytes(hlo_text):
    """Approximate per-device collective wire bytes: for each collective
    instruction, bytes = result size (what each participant receives). The basis
    for the 1-bit Adam comm-volume accounting in PERF.md."""
    total = 0
    for _op, dt, dims in collective_results(hlo_text):
        if dt not in _DTYPE_BYTES:
            continue
        total += _elements(dims) * _DTYPE_BYTES[dt]
    return total


# ----------------------------------------------------------------- per-axis ledger
# A collective instruction names its participant grouping inline:
#   replica_groups={{0,1,2,3},{4,5,6,7}}        explicit groups
#   replica_groups=[4,2]<=[2,4]T(1,0)           iota form: reshape/transpose of
#                                               iota(N) into [groups, group_size]
#   replica_groups={}                           every participant, one group
#   source_target_pairs={{0,1},{1,2}}           collective-permute's equivalent
# Ids are the program's logical device numbers (device-assignment order == the
# flattened mesh.devices order, which on every mesh this repo builds equals the
# global device id — the same convention CommTopology.slice_device_sets uses).
_RG_EXPLICIT_RE = re.compile(r"replica_groups=\{((?:\{[^}]*\},?)*)\}")
_RG_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_STP_RE = re.compile(r"source_target_pairs=\{((?:\{[^}]*\},?)*)\}")


def parse_replica_groups(line):
    """Participant groups of one collective instruction line: a list of int
    tuples, or None when the instruction names no grouping (or the empty
    ``{}`` grouping) — i.e. every participating device is one group."""
    m = _RG_IOTA_RE.search(line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        arr = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            arr = arr.transpose([int(p) for p in m.group(4).split(",")])
        return [tuple(int(v) for v in row) for row in arr.reshape(g, s)]
    m = _RG_EXPLICIT_RE.search(line) or _STP_RE.search(line)
    if m is None or not m.group(1):
        return None
    return [tuple(int(v) for v in grp.split(",") if v)
            for grp in re.findall(r"\{([^}]*)\}", m.group(1))]


def collective_instructions(hlo_text):
    """[(base op, [(dtype, dims)...] produced results, groups-or-None)] for
    every collective instruction, line by line (async ``-start`` folded into
    the base op exactly as in ``collective_counts``)."""
    out = []
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        ty, op, start = m.groups()
        out.append((op, _result_shapes(ty, op, bool(start)),
                    parse_replica_groups(line)))
    return out


def collective_axis_bytes(hlo_text, slice_sets):
    """Split ``collective_bytes`` per network level against a slice
    factorization: ``{"ici": bytes, "dcn": bytes}``.

    ``slice_sets`` is a list of device-id sets (one per slice — see
    ``CommTopology.slice_device_sets``). An instruction accounts as ICI iff
    every one of its replica groups stays inside a single slice; any group
    spanning two slices rides the DCN. Ungrouped instructions (all devices)
    are ICI only on a single-slice factorization. The two buckets sum exactly
    to ``collective_bytes`` on the same program.
    """
    sets = [frozenset(s) for s in slice_sets]
    totals = {"ici": 0, "dcn": 0}
    for _op, shaped, groups in collective_instructions(hlo_text):
        b = sum(_elements(dims) * _DTYPE_BYTES[dt]
                for dt, dims in shaped if dt in _DTYPE_BYTES)
        if groups is None:
            intra = len(sets) <= 1
        else:
            intra = all(any(set(g) <= ss for ss in sets) for g in groups)
        totals["ici" if intra else "dcn"] += b
    return totals


def collective_axis_breakdown(hlo_text, slice_sets):
    """Per-op refinement of ``collective_axis_bytes``:
    ``{op: {"ici": {"count": n, "bytes": b}, "dcn": {...}}}`` with the same
    group-membership rule, so summing the leaves reproduces the two-bucket
    split exactly (the comm-sim CLI report is built from this)."""
    sets = [frozenset(s) for s in slice_sets]
    out = {}
    for op, shaped, groups in collective_instructions(hlo_text):
        b = sum(_elements(dims) * _DTYPE_BYTES[dt]
                for dt, dims in shaped if dt in _DTYPE_BYTES)
        if groups is None:
            intra = len(sets) <= 1
        else:
            intra = all(any(set(g) <= ss for ss in sets) for g in groups)
        lvl = out.setdefault(op, {"ici": {"count": 0, "bytes": 0},
                                  "dcn": {"count": 0, "bytes": 0}})
        lvl["ici" if intra else "dcn"]["count"] += 1
        lvl["ici" if intra else "dcn"]["bytes"] += b
    return out


_DEF_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = ")


# ------------------------------------------------------- metadata / identity
# The profiler's device timeline names slices by (hlo_module, hlo_op); mapping
# them back to the engine's named scopes needs two more module facts: the
# HloModule header name (the trace's ``hlo_module`` key) and each entry
# instruction's ``metadata={op_name="jit(f)/.../ds_grad_bucket0/mul"}`` — the
# jaxpr scope path ``jax.named_scope`` threads through compilation. CPU traces
# carry bare instruction names, so the metadata map is the only scope source
# there; TPU traces prefix scopes in the op name itself and use this map as a
# cross-check.
_MODULE_NAME_RE = re.compile(r"^HloModule\s+([\w.-]+)")
_METADATA_OP_NAME_RE = re.compile(r'metadata=\{[^{}]*op_name="([^"]*)"')


def module_name(hlo_text):
    """The ``HloModule`` header name (e.g. ``jit_loss_and_grad``) — the same
    string the profiler's trace events carry as ``args.hlo_module``. Empty
    when the text has no module header."""
    m = _MODULE_NAME_RE.match(hlo_text)
    return m.group(1) if m else ""


def instruction_names(hlo_text):
    """The name of every instruction definition, across all computations, in the
    order of the text (a scheduled module lists a computation in schedule order)."""
    return [m.group(1) for m in map(_DEF_NAME_RE.match, hlo_text.splitlines()) if m]


def instruction_op_names(hlo_text):
    """{instruction name: metadata op_name} over every definition line that
    carries ``op_name`` metadata, across all computations. The op_name is the
    traced scope path (``jit(fn)/jit(main)/<named scopes>/<primitive>``);
    callers regex their scope tokens out of it."""
    out = {}
    for line in hlo_text.splitlines():
        d = _DEF_NAME_RE.match(line)
        if not d:
            continue
        m = _METADATA_OP_NAME_RE.search(line)
        if m:
            out[d.group(1)] = m.group(1)
    return out


_RESULT_TY_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.-]+ = (\([^)]*\)|\S+) ")


def result_bytes(line):
    """Bytes of one instruction line's produced result(s) — the HBM-write
    proxy the overlap-window pricing charges per scheduled instruction."""
    m = _RESULT_TY_RE.match(line)
    if not m:
        return 0
    return sum(_elements(dims) * _DTYPE_BYTES[dt]
               for dt, dims in _shaped_types(m.group(1))
               if dt in _DTYPE_BYTES)


# --------------------------------------------------------------------- lint surface
# The module header of an optimized program names which donations XLA actually
# honored: `input_output_alias={ {out_idx}: (param_number, {param_idx}, kind) }`.
_ALIAS_HEADER_RE = re.compile(r"input_output_alias=\{((?:[^{}]|\{[^}]*\})*)\}")
_ALIAS_ENTRY_RE = re.compile(r"\{([0-9, ]*)\}:\s*\((\d+),\s*\{([0-9, ]*)\},\s*([\w-]+)\)")


def input_output_aliases(hlo_text):
    """{param_number -> [(output_index, param_index, kind)]} from the module
    header; empty when the program aliases nothing (the header is then absent)."""
    m = _ALIAS_HEADER_RE.search(hlo_text)
    if not m:
        return {}
    out = {}

    def idx(s):
        return tuple(int(x) for x in s.replace(" ", "").split(",") if x)

    for out_idx, param, param_idx, kind in _ALIAS_ENTRY_RE.findall(m.group(1)):
        out.setdefault(int(param), []).append((idx(out_idx), idx(param_idx), kind))
    return out


def _entry_layout_body(hlo_text):
    """'(params...)->result' body of the entry_computation_layout header, via a
    balanced-brace scan (layout annotations like ``{1,0}`` nest braces)."""
    marker = "entry_computation_layout={"
    start = hlo_text.find(marker)
    if start < 0:
        return None
    i, depth = start + len(marker), 1
    while i < len(hlo_text) and depth:
        if hlo_text[i] == "{":
            depth += 1
        elif hlo_text[i] == "}":
            depth -= 1
        i += 1
    return hlo_text[start + len(marker):i - 1]


def _split_top_level(s):
    """Split a type-tuple body on top-level commas (layout braces `{1,0}` and
    nested tuples carry commas of their own)."""
    parts, depth, cur = [], 0, []
    for ch in s:
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return [p.strip() for p in parts if p.strip()]


def entry_parameter_types(hlo_text):
    """[(dtype, dims)] per entry parameter (one entry per parameter, in param-
    number order; a tuple-typed parameter reports its first shaped leaf)."""
    body = _entry_layout_body(hlo_text)
    if body is None or "->" not in body:
        return []
    params = body.split("->", 1)[0].strip()
    if params.startswith("(") and params.endswith(")"):
        params = params[1:-1]
    out = []
    for part in _split_top_level(params):
        shaped = _shaped_types(part)
        out.append(shaped[0] if shaped else (part, ()))
    return out


def entry_result_types(hlo_text):
    """[(dtype, dims)] of the entry computation's results (tuple flattened)."""
    body = _entry_layout_body(hlo_text)
    if body is None or "->" not in body:
        return []
    return _shaped_types(body.split("->", 1)[1])


# ------------------------------------------------------------- buffer table
# The module header's buffer_donor set names parameters the caller donated but
# XLA left unaliased (they are still freed, just not reused in place):
#   buffer_donor={ (1, {}), (3, {}) }
_BUFFER_DONOR_RE = re.compile(r"buffer_donor=\{((?:[^{}]|\{[^}]*\})*)\}")
_BUFFER_DONOR_ENTRY_RE = re.compile(r"\((\d+),\s*\{[0-9, ]*\}\)")


def _type_bytes(shaped):
    return sum(_elements(dims) * _DTYPE_BYTES.get(dt, 0) for dt, dims in shaped)


def entry_buffer_table(hlo_text):
    """Per-buffer view of an optimized program's entry interface — the HBM
    observatory's parsing surface (utils/hbm.py classifies these rows against
    the engine's memory manifest).

    Returns::

        {"parameters": [{"param": i, "leaves": [(dtype, dims, bytes)],
                         "bytes": total, "donated": bool,
                         "aliased_outputs": [output_index tuples]}],
         "results": [{"index": j, "dtype": dt, "dims": dims, "bytes": b,
                      "aliased": bool}],
         "parameter_bytes": int, "result_bytes": int,
         "aliased_result_bytes": int, "unaliased_result_bytes": int}

    Shapes are the post-SPMD per-device shapes of the compiled module (one
    entry parameter per flattened pytree leaf under jit). ``donated`` is true
    when the parameter appears in either donation header (``input_output_alias``
    — donation honored in place — or ``buffer_donor`` — donated, freed, but not
    aliased to an output). A result leaf is ``aliased`` when an input buffer
    backs it, i.e. it occupies no HBM beyond its parameter's bytes."""
    body = _entry_layout_body(hlo_text)
    if body is None or "->" not in body:
        return {"parameters": [], "results": [], "parameter_bytes": 0,
                "result_bytes": 0, "aliased_result_bytes": 0,
                "unaliased_result_bytes": 0}
    params_str, result_str = body.split("->", 1)
    params_str = params_str.strip()
    if params_str.startswith("(") and params_str.endswith(")"):
        params_str = params_str[1:-1]
    aliases = input_output_aliases(hlo_text)
    donors = set()
    m = _BUFFER_DONOR_RE.search(hlo_text)
    if m:
        donors = {int(p) for p in _BUFFER_DONOR_ENTRY_RE.findall(m.group(1))}
    aliased_outputs = {tuple(out_idx)
                       for rows in aliases.values()
                       for out_idx, _param_idx, _kind in rows}
    parameters = []
    for i, part in enumerate(_split_top_level(params_str)):
        shaped = _shaped_types(part)
        leaves = [(dt, dims, _elements(dims) * _DTYPE_BYTES.get(dt, 0))
                  for dt, dims in shaped]
        parameters.append({
            "param": i,
            "leaves": leaves,
            "bytes": sum(b for _dt, _dims, b in leaves),
            "donated": i in aliases or i in donors,
            "aliased_outputs": sorted(out_idx for out_idx, _pi, _k in
                                      aliases.get(i, [])),
        })
    result_str = result_str.strip()
    if result_str.startswith("(") and result_str.endswith(")"):
        result_str = result_str[1:-1]
        result_parts = _split_top_level(result_str)
    else:
        result_parts = [result_str]
    results = []
    for j, part in enumerate(result_parts):
        shaped = _shaped_types(part)
        if not shaped:
            continue
        dt, dims = shaped[0]
        results.append({
            "index": j, "dtype": dt, "dims": dims,
            "bytes": _type_bytes(shaped),
            "aliased": (j,) in aliased_outputs or (() in aliased_outputs
                                                   and len(result_parts) == 1),
        })
    parameter_bytes = sum(p["bytes"] for p in parameters)
    result_bytes = sum(r["bytes"] for r in results)
    aliased_result_bytes = sum(r["bytes"] for r in results if r["aliased"])
    return {
        "parameters": parameters,
        "results": results,
        "parameter_bytes": parameter_bytes,
        "result_bytes": result_bytes,
        "aliased_result_bytes": aliased_result_bytes,
        "unaliased_result_bytes": result_bytes - aliased_result_bytes,
    }


_USE_RE = re.compile(r"%([\w.-]+)")


def temp_allocation_estimate(hlo_text):
    """Analytic peak-temp estimate: a def-to-last-use liveness scan over the
    ENTRY computation's instruction lines. Each non-parameter instruction's
    result bytes go live at its definition line and die after the last line
    referencing it; the estimate is the peak of concurrently-live bytes,
    excluding parameters (argument bytes) and the ROOT tuple (output bytes) —
    i.e. the same bucket ``memory_analysis().temp_size_in_bytes`` measures.

    Fusion-internal buffers are invisible at this granularity (a fusion's
    temp is its result), so the estimate is a scheduling-free LOWER-bound
    companion to the measured temp watermark, good for attribution and
    cross-run comparison rather than exact byte parity."""
    lines = hlo_text.splitlines()
    entry_start = None
    for i in range(len(lines) - 1, -1, -1):
        if lines[i].startswith("ENTRY "):
            entry_start = i
            break
    if entry_start is None:
        return 0
    entry_end = len(lines)
    for i in range(entry_start + 1, len(lines)):
        if lines[i].startswith("}"):
            entry_end = i
            break
    defs = {}       # name -> (def line, bytes)
    last_use = {}   # name -> last line referencing it as an operand
    for i in range(entry_start + 1, entry_end):
        line = lines[i]
        name_m = _DEF_NAME_RE.match(line)
        if not name_m:
            continue
        name = name_m.group(1)
        is_param = " parameter(" in line
        is_root = line.lstrip().startswith("ROOT ")
        if not is_param and not is_root:
            defs[name] = (i, result_bytes(line))
        for used in _USE_RE.findall(line.split("=", 1)[1]):
            if used != name:
                last_use[used] = i
    deaths = {}
    for name, (_def_line, b) in defs.items():
        deaths.setdefault(last_use.get(name, entry_end), []).append(name)
    live = peak = 0
    for i in range(entry_start + 1, entry_end):
        for name, (def_line, b) in defs.items():
            if def_line == i:
                live += b
        peak = max(peak, live)
        for name in deaths.get(i, ()):
            live -= defs[name][1]
    return peak


_F32_DOT_RE = re.compile(r"%?([\w.-]+) = f32\[[^\]]*\][^ ]* dot\(([^)]*)\)")
# optimized HLO annotates operands inline (`convert(bf16[8]{0} %x)`); the
# pre-backend module the dtype lint reads writes bare names (`convert(x.4)`),
# so the operand's source dtype comes from the inline annotation when present
# and the defining instruction otherwise.
_CONVERT_RE = re.compile(
    r"%?([\w.-]+) = ([a-z0-9]+)\[[^\]]*\][^ ]* convert\("
    r"(?:([a-z0-9]+)\[[^\]]*\][^ ]* )?%?([\w.-]+)\)")
_DEF_RE = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = ([a-z0-9]+)\[", re.M)


def _definition_dtypes(hlo_text):
    """{instruction name: result element type} over every definition line."""
    return dict(_DEF_RE.findall(hlo_text))


def _convert_table(hlo_text):
    """{result name: (src dtype, dst dtype, operand name)} for every convert."""
    defs = None
    out = {}
    for name, dst, src, operand in _CONVERT_RE.findall(hlo_text):
        if not src:
            if defs is None:
                defs = _definition_dtypes(hlo_text)
            src = defs.get(operand, "")
        if src:
            out[name] = (src, dst, operand)
    return out


def f32_dots_with_lowp_operands(hlo_text, lowp=("bf16", "f16")):
    """[(dot name, [operand names converted from a low-precision dtype])] for
    every f32 dot at least one of whose operands is the direct result of a
    convert from ``lowp``. The dtype-promotion lint's primary probe: inside a
    declared low-precision compute region, such a dot means XLA (or the traced
    program) silently promoted a matmul the author believed ran on the
    low-precision MXU path."""
    lowp_converts = {name for name, (src, _dst, _op) in
                     _convert_table(hlo_text).items() if src in lowp}
    hits = []
    for dot_name, operands in _F32_DOT_RE.findall(hlo_text):
        names = [tok.split()[-1].lstrip("%")
                 for tok in operands.split(",") if tok.strip()]
        promoted = [n for n in names if n in lowp_converts]
        if promoted:
            hits.append((dot_name, promoted))
    return hits


def lossy_convert_roundtrips(hlo_text):
    """[(first convert name, dtype chain)] for convert pairs d1 -> d2 -> d1
    where the intermediate d2 is NARROWER than d1: a value made a lossy round
    trip (each such pair silently truncates mantissa and usually marks a dtype
    boundary drawn in the wrong place)."""
    converts = _convert_table(hlo_text)
    hits = []
    for name, (src, dst, operand) in sorted(converts.items()):
        up = converts.get(operand)
        if up is None:
            continue
        src0, dst0, _ = up
        if src0 == dst and dst0 == src:  # d1 -> d2 (=src) -> d1 (=dst)
            b_mid = _DTYPE_BYTES.get(src, 0) or 0
            b_end = _DTYPE_BYTES.get(dst, 0) or 0
            if b_mid and b_end and b_mid < b_end:
                hits.append((operand, (dst, src, dst)))
    return hits


# ------------------------------------------------- pricing: operations and bytes
# What every compiled operation HAS to do, from the optimized text alone: the operations
# of its products and a floor of its HBM traffic. Counts only: no peak enters here. An
# operation is an instruction the device runs on its own and a trace shows as an event:
# one of the entry computation or of the body of a ``while``, ``conditional`` or ``call``
# reached from it. What a fused computation holds is priced INTO its fusion. This JAX's
# ``as_text()`` names operands and gives them no type, so every type is read from the
# operand's definition line: one pass over the text fills a name -> instruction table a
# computation, and the pricing reads that.
_TYPE_LEAF_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{([^{}]*)\})?")
_MEMORY_SPACE_RE = re.compile(r"S\((\d+)\)")
_OPCODE_RE = re.compile(r"[\w-]+")
_ATTR_NAME_RE = r"=%?([\w.-]+)"
_CALLS_RE = re.compile("calls" + _ATTR_NAME_RE)
_CALLED_RES = {"fusion": [_CALLS_RE],
               "call": [re.compile("to_apply" + _ATTR_NAME_RE)],
               "while": [re.compile("body" + _ATTR_NAME_RE), re.compile("condition" + _ATTR_NAME_RE)],
               "conditional": [re.compile("(?:true|false)_computation" + _ATTR_NAME_RE)]}
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_DIMS_ATTR_RE = re.compile(r"(\w+)=\{([\d,]*)\}")
_WINDOW_RE = re.compile(r"window=\{([^}]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")
_BRACKET_RE = re.compile(r"[()]")
_COMMENT_RE = re.compile(r"/\*.*?\*/")          # ``/*index=5*/`` before every fifth operand

# no bytes move: views, tuples, and the bookkeeping of a transfer that runs under other
# operations (the wait in a ``-done`` is time over a floor of nothing)
_FREE_OPS = frozenset(("bitcast", "tuple", "get-tuple-element", "parameter", "constant",
                       "copy-start", "copy-done", "async-start", "async-update", "async-done",
                       "after-all", "partition-id", "replica-id", "opt-barrier", "add-dependency"))
# not priced (collectives, the links' work, are told by ``_is_collective``): a kernel has its
# own metric against the ALGORITHM's need, and the control flow's own instruction encloses
# operations that are priced
_UNPRICED_OPS = frozenset(("custom-call", "while", "conditional", "call", "send", "send-done",
                           "recv", "recv-done", "infeed", "outfeed"))
_SLICING_OPS = frozenset(("slice", "dynamic-slice", "gather"))
# what a fused computation evaluates an element at a time: a slice on the far side of
# these reads what it takes and no more
_ELEMENTWISE_VIEWS = frozenset(("bitcast", "reshape", "convert", "copy", "transpose"))


class _Instruction:
    __slots__ = ("name", "type", "opcode", "operands", "attrs", "root")

    def __init__(self, name, type, opcode, operands, attrs, root):
        self.name, self.type, self.opcode = name, type, opcode
        self.operands, self.attrs, self.root = operands, attrs, root


def _closing(text, at):
    """Index of the bracket that closes the one at ``text[at]``."""
    depth = 0
    for m in _BRACKET_RE.finditer(text, at):
        depth += 1 if m.group(0) == "(" else -1
        if depth == 0:
            return m.start()
    return len(text) - 1


def _parse_instruction(line):
    """``[ROOT] %name = TYPE opcode(operands), attributes`` or None."""
    m = _DEF_NAME_RE.match(line)
    if m is None:
        return None
    rest = line[m.end():]
    if rest.startswith("("):                     # a tuple type; its layouts hold brackets too
        end = _closing(rest, 0) + 1
        type_str, rest = rest[:end], rest[end:].lstrip()
    else:
        type_str, _, rest = rest.partition(" ")
    op = _OPCODE_RE.match(rest)
    if op is None or not rest.startswith("(", op.end()):
        return None
    end = _closing(rest, op.end())
    inside = rest[op.end() + 1:end]
    if op.group(0) == "parameter":
        operands = (inside.strip(),)             # its number, not a name
    elif op.group(0) == "constant":
        operands = ()
    else:
        operands = tuple(tok.split()[-1].lstrip("%")
                         for tok in _split_top_level(_COMMENT_RE.sub("", inside)))
    return _Instruction(m.group(1), type_str, op.group(0), operands, rest[end + 1:],
                        line.lstrip().startswith("ROOT"))


def _parse_computations(hlo_text):
    """``({computation: {instruction name: _Instruction}}, entry computation's name)``,
    every table in the order of the text."""
    computations, entry, current = {}, None, None
    for line in hlo_text.splitlines():
        if current is None:
            if line.endswith("{") and " = " not in line and not line.startswith((" ", "HloModule")):
                head = line[:-1].split("(", 1)[0].split()
                if not head:
                    continue
                name = head[-1].lstrip("%")
                current = computations[name] = {}
                if head[0] == "ENTRY":
                    entry = name
        elif line.startswith("}"):
            current = None
        else:
            instruction = _parse_instruction(line)
            if instruction is not None:
                current[instruction.name] = instruction
    return computations, entry


def _leaves(type_str):
    """[(dtype, dims, in HBM)] of a type, tuples flattened. A layout that names a memory
    space (``S(1)``: the compiler put the value in on-chip memory) is not HBM's."""
    out = []
    for dt, dims, layout in _TYPE_LEAF_RE.findall(type_str):
        space = _MEMORY_SPACE_RE.search(layout)
        out.append((dt, tuple(int(d) for d in dims.split(",") if d),
                    space is None or space.group(1) == "0"))
    return out


def _hbm_bytes(type_str):
    return sum(_elements(dims) * _DTYPE_BYTES.get(dt, 0)
               for dt, dims, in_hbm in _leaves(type_str) if in_hbm)


def _dims_attrs(attrs):
    return {k: [int(d) for d in v.split(",") if d] for k, v in _DIMS_ATTR_RE.findall(attrs)}


def _product_types(instruction, table):
    """``((dtype, dims) of the result, of the lhs, of the rhs)`` of a ``dot`` or a
    ``convolution``, the operands' read off their DEFINITIONS; None where one is missing."""
    found = [_leaves(instruction.type)] + [_leaves(table[name].type) if name in table else []
                                           for name in instruction.operands[:2]]
    if len(found) < 3 or not all(found):
        return None
    return [leaves[0][:2] for leaves in found]


def _dot_product(instruction, table):
    """``(flops, [M, K, N, types])`` of one ``dot``: 2 x result elements x contraction."""
    types, dims = _product_types(instruction, table), _dims_attrs(instruction.attrs)
    if types is None or "lhs_contracting_dims" not in dims:
        return 0, None
    (out_t, out_d), (lhs_t, lhs_d), (rhs_t, rhs_d) = types
    try:
        k = _elements(lhs_d[d] for d in dims["lhs_contracting_dims"])
    except IndexError:
        return 0, None
    taken = set(dims.get("rhs_contracting_dims", ())) | set(dims.get("rhs_batch_dims", ()))
    n = _elements(size for d, size in enumerate(rhs_d) if d not in taken)
    elements = _elements(out_d)
    return 2 * elements * k, [elements // max(n, 1), k, n, f"{lhs_t}x{rhs_t}->{out_t}"]


def _window_pairs(n, out, size, stride, lo, lhs_dilate, rhs_dilate):
    """How many (output position, tap) pairs of one spatial dimension read an element of
    the input and not its padding: what a convolution multiplies there. A batched matmul
    written as a convolution (``size=32 pad=31_31`` over an input of 1) has one a position."""
    reach = (n - 1) * lhs_dilate                  # the last input position, dilated
    pairs = 0
    for tap in range(size):
        shift = tap * rhs_dilate - lo             # position = o * stride + shift
        first = max(0, -(shift // stride))        # ceil(-shift / stride)
        last = min(out - 1, (reach - shift) // stride)
        if last >= first:
            pairs += (last - first + 1) // lhs_dilate if lhs_dilate > 1 else last - first + 1
    return pairs


def _window(attrs, rank):
    found = _WINDOW_RE.search(attrs)
    fields = dict(f.split("=", 1) for f in found.group(1).split()) if found else {}

    def per_dim(key, default):
        if key not in fields:
            return [default] * rank
        return [tuple(int(x) for x in d.split("_")) if "_" in d else int(d)
                for d in fields[key].split("x")]
    return (per_dim("size", 1), per_dim("stride", 1), per_dim("pad", (0, 0)),
            per_dim("lhs_dilate", 1), per_dim("rhs_dilate", 1))


def _convolution_product(instruction, table):
    """``(flops, [M, K, N, types])`` of one ``convolution`` (how the chip's compiler
    spells most matmuls): 2 x the multiplications that read no padding."""
    types, labels = _product_types(instruction, table), _DIM_LABELS_RE.search(instruction.attrs)
    if types is None or labels is None:
        return 0, None
    (out_t, out_d), (lhs_t, lhs_d), (rhs_t, rhs_d) = types
    lhs_l, rhs_l, out_l = labels.groups()
    if (len(lhs_l), len(rhs_l), len(out_l)) != (len(lhs_d), len(rhs_d), len(out_d)):
        return 0, None
    rank = len(out_l) - 2
    size, stride, pad, lhs_dilate, rhs_dilate = _window(instruction.attrs, rank)
    try:
        n = out_d[out_l.index("f")]
        macs = out_d[out_l.index("b")] * n * rhs_d[rhs_l.index("i")]
        for d in range(rank):
            macs *= _window_pairs(lhs_d[lhs_l.index(str(d))], out_d[out_l.index(str(d))],
                                  size[d], stride[d], pad[d][0], lhs_dilate[d], rhs_dilate[d])
    except (ValueError, IndexError, TypeError):
        return 0, None
    elements = max(_elements(out_d), 1)
    k = macs // elements if macs % elements == 0 else macs / elements
    return 2 * macs, [elements // max(n, 1), k, n, f"{lhs_t}x{rhs_t}->{out_t}"]


_PRODUCT_OPS = {"dot": _dot_product, "convolution": _convolution_product}


def _products(table, computations, seen=()):
    """(flops, [[M, K, N, types], ...]) of every ``dot`` and ``convolution`` in a
    computation and in the computations its fusions and calls name."""
    flops, found = 0, []
    for instruction in table.values():
        if instruction.opcode in _PRODUCT_OPS:
            f, product = _PRODUCT_OPS[instruction.opcode](instruction, table)
            flops += f
            if product is not None:
                found.append(product)
        elif instruction.opcode in ("fusion", "call"):
            for name in _called(instruction):
                if name in computations and name not in seen:
                    f, more = _products(computations[name], computations, seen + (name,))
                    flops += f
                    found.extend(more)
    return flops, found


def _called(instruction):
    """The computations an instruction names as its body, branches or callee."""
    names = [m.group(1) for regex in _CALLED_RES.get(instruction.opcode, ())
             for m in [regex.search(instruction.attrs)] if m]
    if instruction.opcode == "conditional":
        branches = _BRANCHES_RE.search(instruction.attrs)
        if branches:
            names += [b.strip().lstrip("%") for b in branches.group(1).split(",") if b.strip()]
    return names


def _is_collective(instruction):
    return instruction.opcode.startswith(COLLECTIVE_OPS + ("collective-broadcast", "ragged-all-to-all"))


def _is_kernel(instruction):
    return instruction.opcode == "custom-call" and "tpu_custom_call" in instruction.attrs


def _holds(table, what):
    """Whether a fused or wrapped computation holds a kernel (``_is_kernel``: a custom call
    that only tells the compiler something, ``AssumeGatherIndicesInBound``, is none) or a
    collective (``_is_collective``)."""
    return any(what(i) for i in table.values())


def _through_views(instruction, table):
    """The instruction at the far end of a chain of views and converts."""
    while instruction.opcode in _ELEMENTWISE_VIEWS and instruction.operands:
        source = table.get(instruction.operands[0])
        if source is None:
            break
        instruction = source
    return instruction


def _uses(table):
    """``({name: [(user, operand position)]}, {parameter number: parameter}, root)`` of a
    computation."""
    users, parameters, root = {}, {}, None
    for inner in table.values():
        if inner.opcode == "parameter":
            parameters[int(inner.operands[0])] = inner
        else:
            for position, name in enumerate(inner.operands):
                users.setdefault(name, []).append((inner, position))
        if inner.root:
            root = inner
    return users, parameters, root


def _read_of(parameter, users, full, computations):
    """HBM bytes a fused computation reads of one parameter: what its slices take where
    it is reached through slices alone (its own, or those of a fusion nested in it),
    nothing where it is the buffer a ``dynamic-update-slice`` writes into, else all of it.
    None where the computation hands the parameter on as a view (a nested
    ``bitcast_fusion``): what is read of it is then its user's to say. The smaller where
    in doubt."""
    leaves = _leaves(parameter.type)
    if not full or len(leaves) != 1:
        return full
    width = _DTYPE_BYTES.get(leaves[0][0], 0)
    taken, todo, handed_on = 0, [parameter], False
    while todo:
        value = todo.pop()
        handed_on = handed_on or value.root
        for user, position in users.get(value.name, ()):
            inner = None
            if user.opcode == "fusion" and (_called(user) or [None])[0] in computations:
                inner_users, inner_parameters, _ = _uses(computations[_called(user)[0]])
                if position in inner_parameters:
                    inner = _read_of(inner_parameters[position], inner_users, full, computations)
                else:
                    inner = full
            if user.opcode in _ELEMENTWISE_VIEWS or (user.opcode == "fusion" and inner is None):
                todo.append(user)
            elif user.opcode in _SLICING_OPS and position == 0:
                taken += sum(_elements(dims) for _, dims, _ in _leaves(user.type)) * width
            elif user.opcode == "fusion":
                taken += inner
            elif not (user.opcode == "dynamic-update-slice" and position == 0):
                return full
            if taken >= full:
                return full
    return None if handed_on else taken


def _written_by(root, table):
    """HBM bytes a computation's result costs to write: every leaf once, and a leaf that
    is a ``dynamic-update-slice`` (into a buffer the result aliases) at the update's size."""
    elements = [table.get(n) for n in root.operands] if root.opcode == "tuple" else [root]
    total = 0
    for element in elements:
        if element is None:
            continue
        source = _through_views(element, table)
        update = table.get(source.operands[1]) if (source.opcode == "dynamic-update-slice"
                                                   and len(source.operands) > 1) else None
        for dt, dims, in_hbm in _leaves(element.type):
            if in_hbm:
                if update is not None:
                    dims = max((d for _, d, _ in _leaves(update.type)), key=_elements, default=dims)
                total += _elements(dims) * _DTYPE_BYTES.get(dt, 0)
    return total


def _fusion_bytes(instruction, called, computations):
    """The floor of a fusion's HBM traffic: its result written once, each distinct operand
    read once at what the fused computation takes of it."""
    users, parameters, root = _uses(called)
    written = _written_by(root, called) if root is not None else _hbm_bytes(instruction.type)
    read = {}
    for index, operand in enumerate(instruction.operands):
        parameter = parameters.get(index)
        if parameter is None:
            continue
        full = _hbm_bytes(parameter.type)
        taken = _read_of(parameter, users, full, computations)
        read[operand] = min(full, read.get(operand, 0) + (full if taken is None else taken))
    return written + sum(read.values())


def _plain_bytes(instruction, table):
    """The floor of an unfused instruction's HBM traffic."""
    written = _hbm_bytes(instruction.type)
    sizes = {name: _hbm_bytes(table[name].type) for name in instruction.operands if name in table}
    first = instruction.operands[0] if instruction.operands else None
    if instruction.opcode in _SLICING_OPS and first in sizes:
        sizes[first] = min(sizes[first], written)
    elif instruction.opcode == "dynamic-update-slice" and len(instruction.operands) > 1:
        update = sizes.get(instruction.operands[1], 0)
        sizes[first], written = 0, min(written, update)
    elif instruction.opcode == "scatter" and len(instruction.operands) > 2:
        updates = sizes.get(instruction.operands[2], 0)
        sizes[first], written = 0, min(written, updates)
    return written + sum(sizes.values())


def _async_wrapped(instruction, table, computations):
    """The computation an ``async-start`` wraps, for the start, its updates and its done."""
    while instruction is not None and instruction.opcode.startswith("async"):
        wrapped = _CALLS_RE.search(instruction.attrs)
        if wrapped:
            return computations.get(wrapped.group(1), {})
        instruction = table.get(instruction.operands[0]) if instruction.operands else None
    return {}


def instruction_costs(hlo_text):
    """``{"cost", "products", "collectives"}`` of an optimized program's text.

    ``cost``: ``{instruction: [flops, bytes]}`` for every instruction the device runs as
    an operation of its own. ``flops`` are the PRODUCTS' alone, 2 x result elements x
    contraction of every ``dot`` and ``convolution`` in the instruction or in what it
    fuses (elementwise arithmetic has its bytes for a floor). ``bytes`` is a FLOOR of the
    HBM traffic: each result written once and each distinct operand read once; an operand
    reached only through a slice at what is taken, a ``dynamic-update-slice`` at the
    update's size, a value the compiler keeps in on-chip memory (``S(1)`` in its layout)
    at nothing, views and the bookkeeping of asynchronous copies at nothing. Where the
    text does not settle a reading the smaller is taken: a share built on this reads low.
    Kernels (``tpu_custom_call``), collectives and the control flow's own instructions are
    absent. ``products``: ``{instruction: {"mkn": [[M, K, N, "bf16xbf16->f32"], ...],
    "as": the tuple element a tuple-valued instruction's product fills, or None}}`` for the
    instructions that hold a product. ``collectives``: the instructions that are a
    collective or only wrap one (a reduce-scatter the compiler wrote as a fusion, an
    ``async-start``), which a trace's name does not always tell; a fusion that holds a
    product beside a small collective is priced as the product it is."""
    computations, entry = _parse_computations(hlo_text)
    cost, products, collectives = {}, {}, []
    todo, run = [entry] if entry in computations else [], set()
    while todo:
        name = todo.pop()
        if name in run or name not in computations:
            continue
        run.add(name)
        table = computations[name]
        for instruction in table.values():
            opcode = instruction.opcode
            if opcode in ("while", "conditional", "call"):
                todo.extend(_called(instruction))
            if _is_collective(instruction):
                collectives.append(instruction.name)
            elif opcode in _FREE_OPS:
                if _holds(_async_wrapped(instruction, table, computations), _is_collective):
                    collectives.append(instruction.name)
                else:
                    cost[instruction.name] = [0, 0]
            elif opcode == "fusion":
                called = computations.get((_called(instruction) or [None])[0])
                if called is None or _holds(called, _is_kernel):
                    continue
                flops, found = _products(called, computations)
                if not found and _holds(called, _is_collective):
                    collectives.append(instruction.name)
                    continue
                cost[instruction.name] = [flops, _fusion_bytes(instruction, called, computations)]
                if found:
                    products[instruction.name] = {"mkn": found,
                                                  "as": _product_element(instruction.type, found)}
            elif opcode not in _UNPRICED_OPS:
                flops, product = _PRODUCT_OPS.get(opcode, lambda *_: (0, None))(instruction, table)
                cost[instruction.name] = [flops, _plain_bytes(instruction, table)]
                if product is not None:
                    products[instruction.name] = {"mkn": [product], "as": None}
    return {"cost": cost, "products": products, "collectives": collectives}


def _product_element(type_str, found):
    """For a tuple-valued instruction, the element its largest product fills, as a trace
    would print it (``bf16[8192,16384]``): the first whose size is the product's M x N,
    else the largest. None for an instruction with one result."""
    if not type_str.startswith("("):
        return None
    leaves = _leaves(type_str)
    if not leaves:
        return None
    m, _, n, _ = max(found, key=lambda p: p[0] * p[2])
    best = next((leaf for leaf in leaves if _elements(leaf[1]) == m * n),
                max(leaves, key=lambda leaf: _elements(leaf[1])))
    return f"{best[0]}[{','.join(str(d) for d in best[1])}]"
