"""Traffic kind ``lm_fit_stream``: next-token training of a decoder LM, fed
batch by batch as an iterator to ``MultiLayerNetwork.fit`` so that the
program's dispatch window runs as it does for a user.

The cell's file gives ``rows`` (sequences a step), ``distinct_batches``
(how many different batches the stream cycles through), ``check_steps``
(first steps the reference follows) and ``trace_steps``.  The
configuration's file gives the sizes and the ``precision``.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from benchmark import common, program
from benchmark.check import train as check_train

MHA = ("Wq", "Wk", "Wv", "Wo", "bq", "bk", "bv", "bo")


def lm_batches(seed: int, n_batches: int, rows: int, seq_len: int,
               vocab: int):
    """Token batches ``(x, y)`` of next-token pairs from the seed (the
    benchmark's copy of ``chip_smoke.lm_batches``)."""
    rng = np.random.default_rng(int(seed))
    ids = rng.integers(0, vocab, (n_batches, rows, seq_len + 1)).astype(
        np.int32)
    return [(b[:, :-1], b[:, 1:]) for b in ids]


class Stream:
    """The feed: yields batches in order, round and round, until ``seconds``
    have passed since the first was asked for, or ``count`` were given.  It
    also watches the losses the program's window has drained so far."""

    def __init__(self, batches, net, seconds=None, count=None):
        self.batches, self.net = batches, net
        self.seconds, self.count = seconds, count
        self.given = 0
        self.started = None
        self.bad_losses = set()

    def __iter__(self):
        return self

    def __next__(self):
        import jax
        with jax.profiler.TraceAnnotation("bench.next"):
            now = time.perf_counter()
            if self.started is None:
                self.started = now
            # the program's window notes the last loss it has drained; it
            # starts at no number, with iteration -1
            drained = self.net.last_drained_iteration
            if drained >= 0 and not math.isfinite(
                    self.net.last_drained_score):
                self.bad_losses.add(drained)
            if self.count is not None and self.given >= self.count:
                raise StopIteration
            if self.seconds is not None and \
                    now - self.started >= self.seconds:
                raise StopIteration
            batch = self.batches[self.given % len(self.batches)]
            self.given += 1
            return batch


def program_leaf_name(n_layer: int, layer: str, leaf: str) -> str:
    """The reference's name for the program's ``params[layer][leaf]``."""
    i = int(layer.split("_")[1])
    if i == 0:
        return "wte"
    if i == n_layer + 2:
        return {"W": "head_W", "b": "head_b"}[leaf]
    leaf = leaf[4:] if leaf.startswith("mha_") else leaf
    return f"blocks.{leaf}.{i - 2}"


class Job:
    def __init__(self, cell: dict, cfg: dict, seed: int, devices):
        self.cell, self.cfg, self.seed, self.devices = cell, cfg, seed, devices
        self.net = None
        self.program = None          # its readings of the first steps
        self.batches = lm_batches(seed, cell["distinct_batches"],
                                  cell["rows"], cfg["n_positions"],
                                  cfg["vocab_size"])

    # ------------------------------------------------------------- set-up
    def build(self):
        from deeplearning4j_tpu.models import TransformerLM
        cfg = self.cfg
        if cfg["n_inner"] != 4 * cfg["n_embd"]:
            raise ValueError("TransformerLM builds a 4x MLP only")
        compute = None if cfg["precision"] == "float32" else cfg["precision"]
        return TransformerLM(
            n_layers=cfg["n_layer"], embed=cfg["n_embd"],
            n_heads=cfg["n_head"], seq_len=cfg["n_positions"],
            vocab_size=cfg["vocab_size"], sparse_labels=True,
            compute_dtype=compute, attn_impl="auto",
            updater=program.updater(cfg)).init()

    def _weights(self):
        """The benchmark's weights from the seed, in the program's tree."""
        import jax
        from benchmark.reference import gpt2 as ref
        n = self.cfg["n_layer"]

        @jax.jit
        def as_program(p):
            out = {"layer_0": {"W": p["wte"]},
                   f"layer_{n + 2}": {"W": p["head_W"], "b": p["head_b"]}}
            for i in range(n):
                out[f"layer_{i + 2}"] = {
                    ("mha_" + k if k in MHA else k): a[i]
                    for k, a in p["blocks"].items()}
            return out
        return as_program(ref.init_params(self.cfg,
                                          common.seed_key(self.seed)))

    def _norms(self, tree, minus=None, scale=1.0):
        """Norms of the program's leaves under the reference's names."""
        n = self.cfg["n_layer"]
        return {program_leaf_name(n, layer, leaf): norm * scale
                for (layer, leaf), norm in program.leaf_norms(
                    tree, minus).items()}

    def setup(self):
        import jax
        cell, cfg = self.cell, self.cfg
        t0 = time.perf_counter()
        self.net = net = self.build()
        t_built = time.perf_counter()
        # the optimizer's state is zeros already; layers without weights
        # keep their empty entries
        net.params = {**{k: v for k, v in net.params.items() if not v},
                      **self._weights()}
        jax.block_until_ready(net.params)
        t_weights = time.perf_counter()
        n_check = cell["check_steps"]
        losses, grad_norms = [], None
        beta1 = cfg["optimizer"]["beta1"]
        for i in range(n_check):
            # the window's own call and feed, one step at a time
            net.fit(Stream(self.batches[i:i + 1], net, count=1))
            losses.append(float(net.get_score()))
            if i == 0:
                # Adam's first moment after one step is (1 - beta1) * g
                grad_norms = self._norms(
                    program.optimizer_field(net.opt_state, "mu"),
                    scale=1.0 / (1.0 - beta1))
        delta = self._norms({k: v for k, v in net.params.items() if v},
                            minus=self._weights())
        self.program = {"losses": losses, "grad_norms": grad_norms,
                        "delta_norms": delta}
        jax.block_until_ready(net.params)
        n_params = sum(int(np.prod(a.shape))
                       for a in jax.tree_util.tree_leaves(net.params))
        common.say(f"lm_fit_stream: {n_params / 1e6:.2f} M parameters; the "
                   f"program built its model in {t_built - t0:.1f} s, "
                   f"weights from the seed {t_weights - t_built:.1f} s, "
                   f"first {n_check} steps (compile or cache load, and "
                   f"their readings) {time.perf_counter() - t_weights:.1f} "
                   f"s; losses {[round(v, 4) for v in losses]}")

    # ------------------------------------------------------------- windows
    def _run(self, stream, t_start=None):
        import jax
        net = self.net
        before = net.iteration
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.fit"):
            net.fit(stream)
            jax.block_until_ready(net.params)
        seconds = time.perf_counter() - t0
        steps = net.iteration - before
        failed = len(stream.bad_losses)
        if not math.isfinite(float(net.get_score())):
            failed = max(failed, 1)
        tokens = self.cell["rows"] * self.cfg["n_positions"]
        common.say(f"lm_fit_stream: {steps} steps in {seconds:.3f} s, "
                   f"{steps * tokens / seconds:.1f} tokens/s, "
                   f"last loss {float(net.get_score()):.4f}")
        metrics = {"train_step_ms": 1e3 * seconds / max(steps, 1)}
        if t_start is not None:
            # process start to the first timed dispatch
            metrics["setup_s"] = t0 - t_start
        return {"steps": steps, "attempted": steps, "failed": failed,
                "metrics": metrics}

    def window(self, seconds: float, t_start: float):
        return self._run(Stream(self.batches, self.net, seconds=seconds),
                         t_start)

    def traced_stretch(self):
        return self._run(Stream(self.batches, self.net,
                                count=self.cell["trace_steps"]))

    # --------------------------------------------------------------- after
    def kernels_in_timed_program(self):
        """Names of the program's attention kernels in the lowered train
        step that ran on this cell's batches, read from the program's own
        text (the benchmark's copy of chip_smoke's check)."""
        from deeplearning4j_tpu.nn import compile_cache
        from deeplearning4j_tpu.ops.flash_attention import KERNEL_NAMES
        found = set()
        for _key, entry in compile_cache.iter_trace_cache():
            if entry.name != "train_step":
                continue
            for spec in entry.audit_specs():
                if spec[0][4].shape[0] != self.cell["rows"]:
                    continue
                text = entry.audit_lower(spec).as_text()
                if "tpu_custom_call" in text:
                    found.update(n for n in KERNEL_NAMES if n in text)
        return sorted(found)

    def release(self):
        program.free(self.net)
        self.net = None
        gc.collect()

    def checked_batches(self):
        """The batches of the first steps, which the reference follows."""
        return self.batches[:self.cell["check_steps"]]

    def reference(self, batches, precision="float32", keep_rows=None):
        from benchmark.reference import gpt2 as ref
        return ref.train_steps(self.cfg, common.seed_key(self.seed), batches,
                               precision, keep_rows)

    def check(self):
        """Run once the window has closed and the program's state is freed."""
        extra = {}
        want = self.cell.get("require_kernels")
        if want:
            have = self.kernels_in_timed_program()
            missing = [k for k in want if k not in have]
            extra["kernels_missing"] = (len(missing), 0, not missing)
            if missing:
                common.say(f"lm_fit_stream: the timed train step lacks "
                           f"{missing}")
        self.release()
        read = check_train.readings(self.program,
                                    self.reference(self.checked_batches()))
        common.say(f"lm_fit_stream: worst leaves {read['_where']}")
        return check_train.verdict(read, self.cell.get("limits", {}), extra)

    def flops_per_step(self, flops_module):
        return flops_module.train_step_flops(self.cfg, self.cell["rows"])
