#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip at the cell's own
size, in one process:

    python3 benchmark/tools/readings.py <cell> <first seed> <seeds> [<control seeds> [<which>]]

For each seed: the program's first steps against the float32 reference
(the lower readings).  For the first ``control seeds`` (default 3) also the
reference computed one precision below the configuration's in the
program's place (the control), the reference at the configuration's own
precision, and the reference with part of the batch left out (the fault).
``which`` names those to take, by their first words and commas (default
``control,reference,fault``).  One JSON line per reading on standard output;
with ``READINGS_DUMP=<file>`` in the environment every side's norms, leaf by
leaf, go to that file as JSON lines too, for a look at what a gap is made of.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402
from benchmark.check import train as check_train  # noqa: E402

BELOW = check_train.BELOW


def dump(seed, what, side) -> None:
    path = os.environ.get("READINGS_DUMP")
    if path:
        with open(path, "a") as f:
            f.write(json.dumps({"seed": seed, "what": what, **side}) + "\n")


def main(argv) -> int:
    cell_name, first, count = argv[1], int(argv[2]), int(argv[3])
    n_control = int(argv[4]) if len(argv) > 4 else 3
    which = (argv[5] if len(argv) > 5 else "control,reference,fault").split(",")
    common.keep_writes_inside()
    import jax
    cell = common.load_json("workloads", cell_name + ".json")
    cfg = common.load_json("configs", cell["config"] + ".json")
    traffic = common.load_module("traffic", cell["kind"])
    rows = cell.get("rows") or cfg["batch_size"]
    keep = list(range((rows + 1) // 2))
    for i in range(count):
        seed = first + i * 1_000_003
        job = traffic.Job(cell, cfg, seed, jax.devices())
        job.setup()
        program = job.program
        batches = job.checked_batches()
        job.release()
        reference = job.reference(batches)
        out = {"seed": seed, "what": "program",
               **check_train.readings(program, reference)}
        print(json.dumps(out), flush=True)
        dump(seed, "reference", reference)
        dump(seed, "program", program)
        if i < n_control:
            for what, kw in (
                    ("control " + BELOW[cfg["precision"]],
                     {"precision": BELOW[cfg["precision"]]}),
                    ("reference at " + cfg["precision"],
                     {"precision": cfg["precision"]}),
                    (f"fault rows {keep} of {rows}", {"keep_rows": keep})):
                if what.split()[0] not in which:
                    continue
                other = job.reference(batches, **kw)
                print(json.dumps({"seed": seed, "what": what,
                                  **check_train.readings(other, reference)}),
                      flush=True)
                dump(seed, what, other)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
