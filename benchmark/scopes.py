"""Device busy time per program phase, from the traced run.

The solve programs name their phases with ``jax.named_scope``: a phase
scope is a dotted ``<routine>.<phase>`` name such as ``getrf.panel`` or
``getrs.trsm_lower``, and the phases do not nest.  XLA keeps the scope
path in each HLO op's ``op_name`` metadata.  A v5e trace carries it in
the ``tf_op`` stat of each op's event metadata on the device plane, as
``<op_name>:<op_type>`` (PERF.md, "Reading the trace"); the ``XLA Ops``
events themselves hold only their offset and duration.  An op's phase
is the innermost phase scope on its path, or None for an op under no
phase scope (the drivers' tile/global copies and padding).

``jax.profiler.ProfileData`` shows events and their own stats, not the
event metadata, so ``metadata_paths`` reads the device planes' event
and stat metadata from the ``.xplane.pb`` itself, one entry per distinct
op, and skips the lines that hold the events (2.3M in three hpl solves).
``phase_busy`` is the union of each phase's leaf-op intervals on each
device, clipped to the window and averaged over the chips as
``trace_reduce.reduce``'s ``busy_s`` is.

The readers get ``trace_reduce.reduce``'s dict, which holds op names
but not their scopes, so ``phases`` reopens the traced run's own
profile: the one under the run's ``bench_trace_*`` directory whose
``bench.window`` is the reduced window.
"""

from __future__ import annotations

import functools
import glob
import os
import re
import tempfile

import trace_reduce as tr

STAT = "tf_op"
PHASE = re.compile(r"^[a-z][a-z0-9_]*\.[a-z][a-z0-9_]*$")


def phase_of(path: str):
    """The innermost phase scope on a scope path, or None."""
    found = [p for p in path.split("/") if PHASE.match(p)]
    return found[-1] if found else None


@functools.lru_cache(maxsize=None)
def _xspace():
    """An XSpace message class that keeps only the planes' names and
    their event and stat metadata (the field numbers of
    tsl/profiler/protobuf/xplane.proto); the lines are skipped."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    i64, u64, s, m = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING, F.TYPE_MESSAGE
    shapes = {  # name: [(field, number, type, message type or None)]
        "XStat": [("metadata_id", 1, i64, None), ("str_value", 5, s, None),
                  ("ref_value", 7, u64, None)],
        "XStatMetadata": [("id", 1, i64, None), ("name", 2, s, None)],
        "XEventMetadata": [("id", 1, i64, None), ("name", 2, s, None),
                           ("stats", 5, m, "XStat")],
        "EventEntry": [("key", 1, i64, None),
                       ("value", 2, m, "XEventMetadata")],
        "StatEntry": [("key", 1, i64, None),
                      ("value", 2, m, "XStatMetadata")],
        "XPlane": [("id", 1, i64, None), ("name", 2, s, None),
                   ("event_metadata", 4, m, "EventEntry"),
                   ("stat_metadata", 5, m, "StatEntry")],
        "XSpace": [("planes", 1, m, "XPlane")],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_metadata.proto", package="bench_xplane",
        syntax="proto3")
    for name, fields in shapes.items():
        msg = fd.message_type.add(name=name)
        for fname, num, typ, ref in fields:
            f = msg.field.add(name=fname, number=num, type=typ,
                              label=F.LABEL_OPTIONAL)
            if ref:
                f.type_name = f".bench_xplane.{ref}"
                if not name.endswith("Entry"):  # a map is repeated entries
                    f.label = F.LABEL_REPEATED
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def metadata_paths(path: str) -> dict:
    """{op name: scope path} from the ``tf_op`` stat of the device
    planes' event metadata in the ``.xplane.pb`` at ``path``."""
    with open(path, "rb") as f:
        space = _xspace().FromString(f.read())
    out = {}
    for plane in space.planes:
        if not tr.DEVICE.match(plane.name):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        for e in plane.event_metadata:
            for st in e.value.stats:
                if names.get(st.metadata_id) == STAT:
                    op = (names.get(st.ref_value, "") if st.ref_value
                          else st.str_value)
                    out[tr.op_name(e.value.name)] = op.rsplit(":", 1)[0]
    return out


def scope_of(path: str, names) -> dict:
    """{op name: its phase or None} for the op names in ``names``."""
    paths = metadata_paths(path)
    return {n: phase_of(paths[n]) if n in paths else None for n in names}


def phase_busy(red: dict, scopes: dict) -> dict:
    """{phase or None: busy seconds}, averaged over the devices."""
    total = {}
    for evs in red["ops"].values():
        by = {}
        for s, e, name, _k in evs:
            by.setdefault(scopes.get(name), []).append((s, e))
        for ph, ivs in by.items():
            total[ph] = total.get(ph, 0) + tr.length(tr.union(ivs))
    n = max(1, len(red["ops"]))
    return {ph: t * 1e-9 / n for ph, t in total.items()}


def traced_xplane(window_ns):
    """The path of this run's traced profile whose window is
    ``window_ns``, or None."""
    pattern = os.path.join(tempfile.gettempdir(), "bench_trace_*", "**",
                           "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        if tr.window_of(tr.load(path)) == tuple(window_ns):
            return path
    return None


def phases(run: dict):
    """{phase or None: device busy seconds per solve} of the traced run,
    or None where no op carries a phase scope (a program without them,
    or no device ops in the window)."""
    red = run.get("trace")
    solves = run.get("solves") or 0
    if not red or solves <= 0 or red["busy_s"] <= 0:
        return None
    if "phases" not in run:  # the run's readers share one lookup
        path = traced_xplane(red["window_ns"])
        scopes = {} if path is None else scope_of(
            path, {n for evs in red["ops"].values() for *_x, n, _k in evs})
        run["phases"] = None if not any(scopes.values()) else {
            ph: t / solves for ph, t in phase_busy(red, scopes).items()}
    return run["phases"]


def ms_per_solve(run: dict, names) -> float | None:
    """Device busy ms per solve in the phases ``names``."""
    per = phases(run)
    if per is None:
        return None
    return 1e3 * sum(per.get(ph, 0.0) for ph in names)


def unscoped_share(run: dict) -> float | None:
    """% of device busy time in ops under no phase scope."""
    per = phases(run)
    if per is None:
        return None
    busy = run["trace"]["busy_s"] / run["solves"]
    return 100.0 * per.get(None, 0.0) / busy
