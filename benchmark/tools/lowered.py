#!/usr/bin/env python3
"""Has a change moved a cell's train step?  Lower the step of the LM cells
of a checkout for a described v5e, with no chip and no weights (shapes
only), and compare the text of two checkouts:

    python3 benchmark/tools/lowered.py dump <checkout> <out.json> [<cell> ...]
    python3 benchmark/tools/lowered.py compare <parent.json> <change.json>

``dump`` imports ``benchmark`` and the program from ``<checkout>`` (run it
once a tree, in a process of its own) and writes each cell's lowered
``train_step`` (default: the three LM cells whose kernels a change to the
attention or the routed code can reach).  ``compare`` prints, cell by cell,
whether the texts are equal once every Pallas kernel's body is decoded and
printed without the Python source locations it carries (an edit that moves
a line above a kernel moves those and nothing the chip runs), and the first
lines that differ; its exit code is the number of cells that differ.
"""
from __future__ import annotations

import base64
import difflib
import json
import os
import re
import sys

CELLS = ("gpt2-medium.train-fit", "evabyte-4l.train-fit-long",
         "trinity-mini-5l.train-fit-8k")
BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def stripped(text: str) -> str:
    """``text`` with each kernel's serialized body decoded and printed
    without its locations."""
    from jaxlib.mlir import ir

    def body(m):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            mod = ir.Module.parse(base64.b64decode(m.group(1)))
            asm = mod.operation.get_asm(enable_debug_info=False)
        return "BODY<" + asm.replace("\n", "|") + ">"
    return BODY.sub(body, text)


def dump(root: str, out: str, cells) -> int:
    root = os.path.abspath(root)
    sys.path.insert(0, root)
    os.chdir(root)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    from benchmark import common
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    # the program picks its kernels by the backend it will run on
    jax.default_backend = lambda: "tpu"

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    texts = {}
    for name in cells:
        cell = common.load_json("workloads", name + ".json")
        cfg = common.load_json("configs", cell["config"] + ".json")
        traffic = common.load_module("traffic", cell["kind"])
        held = {}

        def built():
            if hasattr(traffic, "build"):
                net = traffic.build(cfg)
            else:
                job = traffic.Job.__new__(traffic.Job)
                job.cfg, job.cell = cfg, cell
                net = job.build()
            held["net"] = net
            return net.params, net.state, net.opt_state, net._rng
        args = jax.tree_util.tree_map(
            lambda a: spec(a.shape, a.dtype), jax.eval_shape(built))
        rows = cell["rows"]
        t = cfg.get("train_seq_len") or cfg.get("n_positions")
        ids = spec((rows, t), jnp.int32)
        if cell["kind"] == "byte_fit_stream":
            heads = cfg.get("num_pred_heads", 8)
            batch = (ids, spec((rows, t, heads), jnp.int32), None,
                     spec((rows, t, heads), jnp.float32))
        else:
            batch = (ids, ids, None, None)
        step = held["net"]._get_jitted("train_step")
        texts[name] = step.audit_lower((args + batch, {})).as_text()
        print(name, len(texts[name]), "characters,",
              texts[name].count("tpu_custom_call"), "kernel calls",
              flush=True)
    with open(out, "w") as f:
        json.dump(texts, f)
    return 0


def compare(parent: str, change: str) -> int:
    with open(parent) as f:
        a = json.load(f)
    with open(change) as f:
        b = json.load(f)
    moved = 0
    for name in a:
        ta, tb = stripped(a[name]), stripped(b.get(name, ""))
        print(name, "kernel bodies", ta.count("BODY<"), tb.count("BODY<"),
              "equal" if ta == tb else "DIFFERENT")
        if ta == tb:
            continue
        moved += 1
        lines = [line for line in difflib.unified_diff(
            ta.split("\n"), tb.split("\n"), lineterm="", n=0)
            if not line.startswith(("+++", "---", "@@"))]
        for line in lines[:8]:
            print("   ", line[:400])
    return moved


def main(argv) -> int:
    if len(argv) >= 4 and argv[1] == "dump":
        return dump(argv[2], argv[3], argv[4:] or CELLS)
    if len(argv) == 4 and argv[1] == "compare":
        return compare(argv[2], argv[3])
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv))
