"""From a profiler trace to numbers: busy and idle time, kernel time, exposed
collective time, and the breakdown the ledger keeps.

The arithmetic works on a plain structure, so it can be checked on a small recorded
trace (``testdata/``) without a chip:

    {"window": [t0, t1],                                  seconds on the trace's clock
     "devices": {"<plane>": [[op_name, start_s, dur_s], ...]},
     "host": [[span_name, start_s, end_s], ...]}

``load_xplane`` builds it from the ``.xplane.pb`` JAX's profiler writes. Device
operations are named by what the trace prints. The interval arithmetic is a copy of
``deepspeed_tpu/utils/profile_ingest.py``'s (``_union`` and friends), which stays
with the program.
"""

import glob
import os
import re

WINDOW_SPAN = "bench_window"
HOST_SPANS = ("dispatch", "fence", "schedule", "data")     # what the runners put around their calls
COLLECTIVE_RE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|collective-broadcast"
    r"|all_gather|all_reduce|reduce_scatter|all_to_all|collective_permute", re.I)
DEVICE_OP_LINE = "XLA Ops"      # a device plane's line of executed operations


# ------------------------------------------------------------------ intervals
def union(intervals):
    """Merge [start, end) intervals; returns a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def measure(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The part of the disjoint sorted intervals ``a`` that no interval of ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi) that the disjoint sorted ``busy`` leaves."""
    return subtract([[lo, hi]], busy)


# --------------------------------------------------------------------- events
def leaves(events):
    """Drop the events that enclose other events (a ``while`` or a ``conditional``
    around its body), so that every instant counts once and under the innermost name."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    parents, stack = set(), []
    for i in order:
        _, s, d = events[i]
        while stack and s >= events[stack[-1]][1] + events[stack[-1]][2] - 1e-12:
            stack.pop()
        if stack and s + d <= events[stack[-1]][1] + events[stack[-1]][2] + 1e-12:
            parents.add(stack[-1])
        stack.append(i)
    return [events[i] for i in order if i not in parents]


def short_name(text):
    """The chip's trace names a device operation by its whole HLO instruction,
    ``%fusion.12 = bf16[6400,1600]{1,0:T(8,128)(2,1)} fusion(...), kind=...``. Keep what
    tells operations apart: ``fusion.12 bf16[6400,1600] fusion``, and the custom call's
    target where there is one."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:96]
    rest = rest.lstrip()
    if rest.startswith("("):                      # a tuple type: name its first element
        depth = end = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        first = re.match(r"\(+([A-Za-z0-9]+\[[^\]]*\])", rest)
        kind, after = (first.group(1) if first else "()"), rest[end + 1:].lstrip()
    else:
        kind, _, after = rest.partition(" ")
        kind = kind.split("{")[0]
    opcode = re.match(r"[A-Za-z][A-Za-z0-9_.\-]*", after)
    label = f"{head.strip().lstrip('%')} {kind} {opcode.group(0) if opcode else '?'}"
    target = re.search(r'custom_call_target="([^"]+)"', text)
    if target:
        label += " " + target.group(1)
    elif "tpu_custom_call" in text:
        label += " tpu_custom_call"
    return label


def op_group(name):
    """The row operations are summed under: the short name without the counter XLA
    appends, so ``fusion.123 bf16[6400,1600] fusion`` and ``fusion.7 bf16[6400,1600]
    fusion`` are one row, ``fusion bf16[6400,1600]``."""
    parts = name.split(" ")
    base = re.sub(r"[.]\d+", "", parts[0]) or parts[0]
    if len(parts) < 3:
        return base
    out = f"{base} {parts[1]}"
    return out + " " + " ".join(parts[3:]) if len(parts) > 3 else out


def is_collective(name):
    return bool(COLLECTIVE_RE.search(name))


def _intervals(events, lo, hi):
    return clip([[s, s + d] for _, s, d in events], lo, hi)


class Reduced:
    """One trace reduced over its window."""

    def __init__(self, trace):
        self.host = trace.get("host", [])
        everything = dict(sorted(trace["devices"].items()))
        self.devices = {k: leaves(v) for k, v in everything.items()}
        lo, hi = trace.get("window") or (None, None)
        if lo is None:
            starts = [s for ev in everything.values() for _, s, _ in ev]
            ends = [s + d for ev in everything.values() for _, s, d in ev]
            lo, hi = (min(starts), max(ends)) if starts else (0.0, 0.0)
        self.lo, self.hi = lo, hi
        self.window_s = hi - lo
        # busy is the union of every event: a ``while`` between two of its body's
        # operations is the device at work, not the device idle
        self.busy = {k: union(_intervals(ev, lo, hi)) for k, ev in everything.items()}

    # busy and idle
    def busy_s(self):
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.busy:
            return None
        return sum(measure(b) for b in self.busy.values()) / len(self.busy)

    def idle_share(self):
        if not self.busy or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    # kernels
    def op_seconds(self, match):
        """Summed device time of the operations whose name ``match`` accepts, averaged
        over the devices, and their number on the first device."""
        if not self.devices:
            return None, 0
        total, count = 0.0, 0
        for n, (_, ev) in enumerate(self.devices.items()):
            mine = [e for e in ev if match(e[0])]
            total += measure(_intervals(mine, self.lo, self.hi))
            if n == 0:
                count = sum(1 for _, s, d in mine if s >= self.lo and s + d <= self.hi)
        return total / len(self.devices), count

    # collectives
    def collective_exposed_s(self):
        """Seconds in which a collective ran on a device and no compute did, averaged
        over the devices; None where the trace holds no collective."""
        if not self.devices:
            return None
        total, seen = 0.0, False
        for ev in self.devices.values():
            coll = union(_intervals([e for e in ev if is_collective(e[0])], self.lo, self.hi))
            comp = union(_intervals([e for e in ev if not is_collective(e[0])], self.lo, self.hi))
            seen = seen or bool(coll)
            total += measure(subtract(coll, comp))
        return total / len(self.devices) if seen else None

    # the ledger's breakdown
    def breakdown(self, top=10):
        if not self.devices:
            return None
        first = next(iter(self.devices))
        by_op = {}
        for name, s, d in self.devices[first]:
            got = measure(clip([[s, s + d]], self.lo, self.hi))
            if got > 0:
                key = op_group(name)
                by_op[key] = by_op.get(key, 0.0) + got
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(gaps(self.busy[first], self.lo, self.hi), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[self.host_label(s, e), e - s] for s, e in idle]}

    def host_label(self, s, e):
        """The innermost of the harness's host spans that covers the middle of [s, e)."""
        mid, best = (s + e) / 2, None
        for name, hs, he in self.host:
            if name in HOST_SPANS and hs <= mid < he:
                if best is None or he - hs < best[1]:
                    best = (name, he - hs)
        return best[0] if best else "untraced"


# --------------------------------------------------------------------- xplane
def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path, device_prefix="/device:TPU:"):
    """The structure above from an ``.xplane.pb``: the operations of every device
    plane's "XLA Ops" line, the harness's spans from the host's Python threads, and
    the window its ``bench_window`` span marks."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    trace = {"window": None, "devices": {}, "host": []}
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            for line in plane.lines:
                if line.name == DEVICE_OP_LINE:
                    trace["devices"][plane.name] = [
                        [short_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        s = e.start_ns * 1e-9
                        if e.name == WINDOW_SPAN:
                            trace["window"] = [s, s + e.duration_ns * 1e-9]
                        else:
                            trace["host"].append([e.name, s, s + e.duration_ns * 1e-9])
    return trace


def describe_xplane(path, per_line=12):
    """What a trace holds, for a first look by hand: planes, lines, and a few events."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name} events={len(events)}")
            for e in events[:per_line]:
                stats = {k: (v if not isinstance(v, str) else v[:80]) for k, v in e.stats}
                out.append(f"    {e.name[:120]} start={e.start_ns} dur={e.duration_ns} {stats}")
    return "\n".join(out)
