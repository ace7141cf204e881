#!/usr/bin/env python3
"""The verdicts a cell's limits give, on the chip at the cell's own size, in
one process, for a traffic kind that compares more than ``check/train.py``'s
gaps (``compare()``: the routing's agreement beside them), which
``readings.py`` does not read:

    python3 benchmark/tools/verdicts.py <cell> <first seed> <seeds> [<faults>]

For each seed: the program's first step against the float32 reference (which
must be correct), then in the program's place the reference computed one
precision below the configuration's (the control), the reference at the
configuration's own precision, and the reference with each of ``faults``
planted (names the family's reference knows, by commas; default none), all
of which but the configuration's own precision must come out not correct.
One JSON line per verdict on standard output: ``correct`` and every number
compared beside its limit.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402
from benchmark.check import train as check_train  # noqa: E402


def main(argv) -> int:
    cell_name, first, count = argv[1], int(argv[2]), int(argv[3])
    faults = [f for f in (argv[4] if len(argv) > 4 else "").split(",") if f]
    common.keep_writes_inside()
    import jax
    cell = common.load_json("workloads", cell_name + ".json")
    cfg = common.load_json("configs", cell["config"] + ".json")
    traffic = common.load_module("traffic", cell["kind"])
    below = check_train.BELOW[cfg["precision"]]
    for i in range(count):
        seed = first + i * 1_000_003
        job = traffic.Job(cell, cfg, seed, jax.devices())
        job.setup()
        program = job.program
        batches = job.checked_batches()
        job.release()
        reference = job.reference(batches)
        sides = [("program", program)]
        sides += [(what, job.reference(batches, **kw)) for what, kw in
                  [("control " + below, {"precision": below}),
                   ("reference at " + cfg["precision"],
                    {"precision": cfg["precision"]})]
                  + [("fault " + f, {"fault": f}) for f in faults]]
        for what, side in sides:
            correct, compared, _ = job.compare(side, reference)
            print(json.dumps({"seed": seed, "what": what,
                              "correct": bool(correct), **compared}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
