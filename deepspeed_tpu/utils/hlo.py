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


# ------------------------------------------------------- async start/done pairs
# Post-scheduling HLO splits an overlappable collective into a `-start` that
# launches the transfer and a `-done` that blocks on it; every instruction the
# scheduler placed between the two runs concurrently with the wire, so that
# window is what a collective has to hide under. Two syntactic forms exist:
# dedicated start/done ops (`all-reduce-start` / `all-reduce-done`) and the
# generic wrapper (`async-start(...), calls=%comp` holding the collective
# inside the called computation, optionally chained through `async-update`).

_DEF_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = ")
_ASYNC_DONE_RE = re.compile(
    r"= .*?(" + "|".join(COLLECTIVE_OPS) + r"|async)-done\(([^)]*)\)")
_ASYNC_UPDATE_RE = re.compile(r"= .*?async-update\(([^)]*)\)")
_ASYNC_WRAPPER_RE = re.compile(r"= .*? async-start\(")
_CALLS_RE = re.compile(r"calls=%?([\w.-]+)")
_COMP_HEADER_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.-]+)\s+(?:\([^{]*\))?\s*"
                             r"(?:->\s*[^{]*)?\{\s*$")


def _operand_name(operand_text):
    """Instruction name from a (possibly type-annotated) operand: both
    ``f32[1024]{0} %ars`` and ``%ars``/``ars`` yield ``ars``."""
    toks = operand_text.strip().split()
    return toks[-1].lstrip("%") if toks else ""


def _called_computation_window(lines, comp_name):
    """Line-index range (start, stop) of computation ``comp_name``'s body."""
    for i, line in enumerate(lines):
        m = _COMP_HEADER_RE.match(line)
        if m and m.group(1) == comp_name:
            for j in range(i + 1, len(lines)):
                if lines[j].strip().startswith("}"):
                    return i + 1, j
            return i + 1, len(lines)
    return None


def parse_async_pairs(hlo_text):
    """Pair every async collective ``-start`` with its ``-done`` across the
    program text. Returns one dict per pair, in done order::

        {"op": base op, "name": start instruction name, "done": done name,
         "start_line": int, "done_line": int,   # indices into splitlines()
         "bytes": per-device transfer bytes, "groups": replica groups or None}

    Dedicated forms (``all-reduce-start`` ...) read bytes/groups off the start
    line with the same tuple conventions as ``collective_results``; generic
    ``async-start`` wrappers resolve ``calls=`` to the inner collective, and
    ``async-update`` chains forward to the original start. A ``-done`` whose
    operand resolves to no known start raises ``ValueError`` — a malformed
    program must fail loudly, not silently drop a collective from the ledger.
    """
    lines = hlo_text.splitlines()
    starts = {}   # start name -> pair dict (without done fields yet)
    alias = {}    # async-update result name -> upstream operand name
    pairs = []
    for i, line in enumerate(lines):
        m_op = _OP_RE.search(line)
        if m_op and m_op.group(3):  # dedicated `<op>-start`
            name_m = _DEF_NAME_RE.match(line)
            if not name_m:
                continue
            ty, op, _ = m_op.groups()
            b = sum(_elements(dims) * _DTYPE_BYTES[dt]
                    for dt, dims in _result_shapes(ty, op, True)
                    if dt in _DTYPE_BYTES)
            starts[name_m.group(1)] = {
                "op": op, "name": name_m.group(1), "start_line": i,
                "bytes": b, "groups": parse_replica_groups(line),
                "inner_line": None}
            continue
        if _ASYNC_WRAPPER_RE.search(line):  # generic wrapper form
            name_m = _DEF_NAME_RE.match(line)
            calls_m = _CALLS_RE.search(line)
            if not name_m:
                continue
            op, b, groups, inner_line = None, 0, None, None
            if calls_m:
                window = _called_computation_window(lines, calls_m.group(1))
                if window:
                    for k in range(window[0], window[1]):
                        m_in = _OP_RE.search(lines[k])
                        if m_in:
                            ty, op, is_start = m_in.groups()
                            b = sum(_elements(dims) * _DTYPE_BYTES[dt]
                                    for dt, dims in
                                    _result_shapes(ty, op, bool(is_start))
                                    if dt in _DTYPE_BYTES)
                            groups = parse_replica_groups(lines[k])
                            inner_line = k
                            break
            if op is not None:
                starts[name_m.group(1)] = {
                    "op": op, "name": name_m.group(1), "start_line": i,
                    "bytes": b, "groups": groups, "inner_line": inner_line}
            continue
        m_upd = _ASYNC_UPDATE_RE.search(line)
        if m_upd:
            name_m = _DEF_NAME_RE.match(line)
            if name_m:
                alias[name_m.group(1)] = _operand_name(m_upd.group(1))
            continue
        m_done = _ASYNC_DONE_RE.search(line)
        if m_done:
            done_m = _DEF_NAME_RE.match(line)
            operand = _operand_name(m_done.group(2))
            seen = set()
            while operand in alias and operand not in seen:  # update chains
                seen.add(operand)
                operand = alias[operand]
            pair = starts.pop(operand, None)
            if pair is None:
                raise ValueError(
                    f"async {m_done.group(1)}-done "
                    f"{done_m.group(1) if done_m else '<unnamed>'!r} has no "
                    f"matching -start for operand {operand!r}")
            pair["done"] = done_m.group(1) if done_m else ""
            pair["done_line"] = i
            pairs.append(pair)
    return pairs


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


# per-instruction cost estimates for the overlap-window pricing: a window's
# compute capacity is what the scheduler placed between -start and -done,
# priced as max(dot flops / peak, result bytes / HBM bandwidth)
_DOT_LINE_RE = re.compile(r"= (\S+) dot\(([^)]*)\)")
_LHS_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_RESULT_TY_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.-]+ = (\([^)]*\)|\S+) ")


def dot_flops_estimate(line):
    """2 * result_elements * contraction_size for one ``dot`` instruction line,
    reading the contraction off the lhs operand's inline type annotation
    (optimized HLO always annotates). 0 when the line is not an annotated dot
    — the overlap estimate stays conservative (no phantom compute credit)."""
    m = _DOT_LINE_RE.search(line)
    if not m:
        return 0
    result = _shaped_types(m.group(1))
    cd = _LHS_CDIMS_RE.search(line)
    if not result or not cd:
        return 0
    operands = _split_top_level(m.group(2))
    lhs = _shaped_types(operands[0]) if operands else []
    if not lhs:
        return 0
    cdims = [int(d) for d in cd.group(1).split(",") if d]
    contraction = 1
    for d in cdims:
        if d >= len(lhs[0][1]):
            return 0
        contraction *= lhs[0][1][d]
    return 2 * _elements(result[0][1]) * contraction


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
