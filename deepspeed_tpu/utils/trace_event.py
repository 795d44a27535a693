"""Shared Chrome/Perfetto ``trace_event`` writer.

The CLIs that export timelines in the Chrome trace_event JSON format —
``ds-tpu timeline`` (pipeline instruction spans, utils/pipeline_trace.py),
``ds-tpu serve-timeline`` (serving request lifecycles, serve/request_trace.py),
``ds-tpu goodput`` and ``ds-tpu cluster-dump`` — build on this one copy of the
event constructors and the byte-stable serializer.

The golden-file contract lives in :func:`serialize_trace`: sorted keys, no
whitespace, so the emitted bytes are a pure function of the event dicts'
key/value sets — construction order never matters. The helpers below build
exactly the dict shapes the pre-dedup writers emitted, which is what keeps
``pipeline_timeline_2x4.trace.json`` and ``serve_timeline_64.trace.json``
byte-identical across the refactor.
"""

import json

__all__ = ["serialize_trace", "trace_envelope", "load_bundle",
           "process_name_event", "thread_meta_events", "complete_slice",
           "counter_event", "instant_event"]


def serialize_trace(trace):
    """Byte-stable serialization (sorted keys, no whitespace) — the golden-file
    contract of the timeline exporter tests."""
    return json.dumps(trace, sort_keys=True, separators=(",", ":"))


def trace_envelope(events, generator, **other_data):
    """The top-level trace_event JSON object: ``traceEvents`` plus an
    ``otherData`` block naming the generator (and any exporter-specific
    facts, e.g. stage count or the iteration timebase)."""
    other = {"generator": generator}
    other.update(other_data)
    return {"traceEvents": events, "displayTimeUnit": "ms", "otherData": other}


def load_bundle(path, kind):
    """Read a dump JSON and return the bundle of ``kind`` — either the file
    itself (``data["kind"] == kind``) or a bundle embedded under the ``kind``
    key of a flight-recorder dump. None when neither form is present."""
    with open(path) as f:
        data = json.load(f)
    if data.get("kind") == kind:
        return data
    embedded = data.get(kind)
    if isinstance(embedded, dict) and embedded.get("kind") == kind:
        return embedded
    return None


def process_name_event(pid, name, tid=0):
    return {"ph": "M", "pid": pid, "tid": tid, "name": "process_name",
            "args": {"name": name}}


def thread_meta_events(pid, tid, name, sort_index=None):
    """The (thread_name, thread_sort_index) metadata pair for one track;
    the sort_index event is omitted when ``sort_index`` is None."""
    events = [{"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
               "args": {"name": name}}]
    if sort_index is not None:
        events.append({"ph": "M", "pid": pid, "tid": tid,
                       "name": "thread_sort_index",
                       "args": {"sort_index": sort_index}})
    return events


def complete_slice(pid, tid, ts, dur, name, cat, args, cname=None):
    """A complete ("X") slice; zero-length spans render as 1 us so they stay
    visible in the Perfetto UI. ``cname`` picks a reserved color name."""
    ev = {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": max(dur, 1),
          "cat": cat, "name": name, "args": args}
    if cname:
        ev["cname"] = cname
    return ev


def counter_event(pid, tid, ts, name, args):
    return {"ph": "C", "pid": pid, "tid": tid, "ts": ts, "name": name,
            "args": args}


def instant_event(pid, tid, ts, name, args):
    """A thread-scoped ("s": "t") instant marker."""
    return {"ph": "i", "pid": pid, "tid": tid, "ts": ts, "s": "t",
            "name": name, "args": args}
