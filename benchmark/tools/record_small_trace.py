#!/usr/bin/env python3
"""Record the small trace that the tests read: the test-sized cell under
``tests/benchmark/data`` run on the chip with ``--trace 1``'s path, the
``.xplane.pb`` copied to the directory given.

    python3 benchmark/tools/record_small_trace.py <cell file> <config file> <out dir>
"""
from __future__ import annotations

import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, run, trace_reduce  # noqa: E402


def main(argv) -> int:
    with open(argv[1]) as f:
        cell = json.load(f)
    with open(argv[2]) as f:
        cfg = json.load(f)
    common.keep_writes_inside()
    import jax
    os.environ["BENCHMARK_KEEP_TRACE"] = "1"
    manifest = common.load_manifest()
    name = manifest["workloads"][0]["name"]
    result = run.execute(name, 7, 1.0, True, jax.devices()[:1],
                         manifest=manifest, cell=cell, cfg=cfg)
    path = trace_reduce.newest_xplane(os.path.join(common.OUT, "trace", name))
    os.makedirs(argv[3], exist_ok=True)
    shutil.copy(path, os.path.join(argv[3], "small.xplane.pb"))
    with open(os.path.join(argv[3], "small.result.json"), "w") as f:
        json.dump(result, f)
    print(os.path.getsize(path), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
