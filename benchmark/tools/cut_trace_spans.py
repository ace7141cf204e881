#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to what ``program_spans`` reads too,
so that a small one with the program's spans can sit among the tests' data:

    python3 benchmark/tools/cut_trace_spans.py <in.xplane.pb> <out.xplane.pb>

As ``cut_trace.py``, but keeps the host's ``dl4j.`` events beside the
``bench.`` ones and, of the device planes' statistics, the ``tf_op`` of each
operation's metadata (its name scope).  Needs the protocol's Python module,
which TensorFlow brings; only the two cutting tools do.
"""
from __future__ import annotations

import sys

KEPT_HOST = ("bench.", "dl4j.")
KEPT_STAT = "tf_op"


def main(argv) -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(argv[1], "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = plane.name.startswith("/device:")
        kept = xplane_pb2.XPlane(id=plane.id, name=plane.name)
        used = set()
        for line in plane.lines:
            events = [e for e in line.events if device or plane.event_metadata[
                e.metadata_id].name.startswith(KEPT_HOST)]
            if not events:
                continue
            new = kept.lines.add(id=line.id, name=line.name,
                                 display_name=line.display_name,
                                 timestamp_ns=line.timestamp_ns,
                                 duration_ps=line.duration_ps)
            for e in events:
                new.events.add(metadata_id=e.metadata_id,
                               offset_ps=e.offset_ps,
                               duration_ps=e.duration_ps)
                used.add(e.metadata_id)
        scope_ids = [i for i, m in plane.stat_metadata.items()
                     if m.name == KEPT_STAT]
        for i in used:
            meta = plane.event_metadata[i]
            kept.event_metadata[i].id = meta.id
            kept.event_metadata[i].name = meta.name
            for stat in meta.stats:
                if device and stat.metadata_id in scope_ids:
                    kept.event_metadata[i].stats.add().CopyFrom(stat)
        if device:
            for i in scope_ids:
                kept.stat_metadata[i].CopyFrom(plane.stat_metadata[i])
        if kept.lines:
            out.planes.append(kept)
    with open(argv[2], "wb") as f:
        f.write(out.SerializeToString())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
