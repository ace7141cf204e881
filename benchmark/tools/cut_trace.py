#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to what ``trace_reduce`` reads, so
that a small one can sit among the tests' data:

    python3 benchmark/tools/cut_trace.py <in.xplane.pb> <out.xplane.pb>

Keeps the device planes' lines as they are and, of the host's, only the
events whose name starts with ``bench.``; drops every statistic.  Needs the
protocol's Python module, which TensorFlow brings; only this tool does.
"""
from __future__ import annotations

import sys


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
                e.metadata_id].name.startswith("bench.")]
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
        for i in used:
            meta = plane.event_metadata[i]
            kept.event_metadata[i].id = meta.id
            kept.event_metadata[i].name = meta.name
        if kept.lines:
            out.planes.append(kept)
    with open(argv[2], "wb") as f:
        f.write(out.SerializeToString())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
