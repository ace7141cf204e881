"""Traffic kind ``byte_fit_stream``: multi-byte-prediction training of a
byte-level decoder (the ``evabyte`` family), fed batch by batch as an
iterator to ``MultiLayerNetwork.fit`` exactly as ``lm_fit_stream`` feeds
its model (the feed is that kind's ``Stream``), and reporting the same
``train_step_ms`` and ``setup_s``.

The cell's file gives ``rows`` (sequences a step), ``distinct_batches``,
``check_steps`` (1: the reference keeps no Adam moments) and
``trace_steps``.  The configuration's file gives the sizes, the
``precision`` and ``train_seq_len``, the bytes in a sequence.  A batch is
``(ids, targets, None, label mask)``: byte ids ``[rows, t]`` uniform on the
vocabulary from the seed, ``targets[r, t, n] = ids[r, t + 1 + n]``, the mask
0 where that byte lies past the row's end.

The state nearly fills the chip (12 bytes a parameter), so set-up never
holds two copies of the weights beside it: the network's own initial
weights are dropped before the benchmark's are made, and the change of the
parameters is measured against weights made again from the seed, sliced
inside the program that takes the norms.
"""
from __future__ import annotations

import functools
import gc
import math
import time

import numpy as np

from benchmark import common, program
from benchmark.check import train as check_train

_lm = common.load_module("traffic", "lm_fit_stream")
Stream, kernels_in_timed_program = _lm.Stream, _lm.Job.kernels_in_timed_program

# the program's name for a block's leaf -> the reference's
BLOCK_LEAVES = {"mha_Wq": "Wq", "mha_Wk": "Wk", "mha_Wv": "Wv",
                "mha_Wo": "Wo", "mha_phi": "phi", "mha_mu": "mu",
                "Wg": "Wg", "W1": "W1", "W2": "W2", "ln1_g": "ln1_g",
                "ln2_g": "ln2_g"}


class TimedStream(Stream):
    """The feed, noting when each batch was asked for: the program asks as
    its window of steps in flight gets room, so a run that loses steps
    shows in the waits whether one stall took them or all steps ran slow."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.asked = []

    def __next__(self):
        self.asked.append(time.perf_counter())
        return super().__next__()

    def longest_waits(self, n=3):
        """``[(seconds, before which batch), ...]``, the longest first."""
        waits = np.diff(self.asked)
        return [(round(float(waits[i]), 3), int(i) + 1)
                for i in np.argsort(-waits)[:n]]


def byte_batches(seed: int, n_batches: int, rows: int, seq_len: int,
                 vocab: int, heads: int):
    """``(ids, targets, None, label mask)`` batches from the seed."""
    rng = np.random.default_rng(int(seed))
    ids = rng.integers(0, vocab, (n_batches, rows, seq_len)).astype(np.int32)
    at = np.arange(seq_len)[:, None] + 1 + np.arange(heads)[None, :]
    mask = np.ascontiguousarray(np.broadcast_to(
        (at < seq_len).astype(np.float32), (rows, seq_len, heads)))
    at = np.minimum(at, seq_len - 1)
    return [(b, np.ascontiguousarray(b[:, at]), None, mask) for b in ids]


def reference_name(n_layer: int, layer: str, leaf: str) -> str:
    """The reference's name for the program's ``params[layer][leaf]``: the
    embedding, ``n_layer`` blocks, the final norm, the head."""
    i = int(layer.split("_")[1])
    if i == 0:
        return "wte"
    if i == n_layer + 1:
        return "norm_g"
    if i == n_layer + 2:
        return "head_W"
    return f"blocks.{BLOCK_LEAVES[leaf]}.{i - 1}"


def as_program(n_layer: int, p: dict) -> dict:
    """The reference's tree in the program's layout."""
    out = {"layer_0": {"W": p["wte"]},
           f"layer_{n_layer + 1}": {"gain": p["norm_g"]},
           f"layer_{n_layer + 2}": {"W": p["head_W"]}}
    for i in range(n_layer):
        out[f"layer_{i + 1}"] = {mine: p["blocks"][theirs][i]
                                 for mine, theirs in BLOCK_LEAVES.items()}
    return out


class Job:
    def __init__(self, cell: dict, cfg: dict, seed: int, devices):
        self.cell, self.cfg, self.seed, self.devices = cell, cfg, seed, devices
        self.net = None
        self.program = None          # its readings of the first step
        self.batches = byte_batches(seed, cell["distinct_batches"],
                                    cell["rows"], cfg["train_seq_len"],
                                    cfg["vocab_size"], cfg["num_pred_heads"])

    # ------------------------------------------------------------- set-up
    def build(self):
        from deeplearning4j_tpu.models import EvaByteLM
        cfg = self.cfg
        compute = None if cfg["precision"] == "float32" else cfg["precision"]
        return EvaByteLM(
            vocab_size=cfg["vocab_size"], seq_len=cfg["train_seq_len"],
            embed=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
            ffn_hidden=cfg["intermediate_size"],
            pred_heads=cfg["num_pred_heads"], window=cfg["window_size"],
            chunk=cfg["chunk_size"], rope_theta=float(cfg["rope_theta"]),
            eps=cfg["rms_norm_eps"], attn_impl="auto",
            cache_mode=cfg["cache_mode"], compute_dtype=compute,
            updater=program.updater(cfg)).init()

    def _seed_weights(self):
        """The benchmark's weights from the seed, as the reference holds
        them (the blocks' leaves stacked)."""
        from benchmark.reference import evabyte as ref
        return ref.init_params(self.cfg, common.seed_key(self.seed))

    def _named(self, norms: dict, scale=1.0) -> dict:
        n = self.cfg["num_hidden_layers"]
        return {reference_name(n, layer, leaf): norm * scale
                for (layer, leaf), norm in norms.items()}

    def _delta_norms(self, params) -> dict:
        """Norms of ``params`` minus the seed's weights, by the program's
        leaves; the seed's weights are sliced inside the one program, so no
        second copy in the program's layout is ever held."""
        import jax
        import jax.numpy as jnp
        n = self.cfg["num_hidden_layers"]

        @jax.jit
        def norms(now, seed_weights):
            then = as_program(n, seed_weights)
            return {k: {kk: jnp.sqrt(jnp.sum(jnp.square(a - then[k][kk])))
                        for kk, a in v.items()} for k, v in now.items()}
        host = jax.device_get(norms({k: v for k, v in params.items() if v},
                                    self._seed_weights()))
        return {(k, kk): float(a) for k, v in host.items()
                for kk, a in v.items()}

    def setup(self):
        import jax
        cell, cfg = self.cell, self.cfg
        if cell["check_steps"] != 1:
            raise ValueError("byte_fit_stream follows one step: its "
                             "reference keeps no Adam moments")
        t0 = time.perf_counter()
        self.net = net = self.build()
        t_built = time.perf_counter()
        # the optimizer's state is zeros already; the network's own weights
        # go before the seed's come, so that the two never lie side by side
        empty = {k: v for k, v in net.params.items() if not v}
        net.params = None
        n = cfg["num_hidden_layers"]
        net.params = {**empty, **jax.jit(functools.partial(as_program, n))(
            self._seed_weights())}
        jax.block_until_ready(net.params)
        t_weights = time.perf_counter()
        # the window's own call and feed, one step
        net.fit(Stream(self.batches[:1], net, count=1))
        losses = [float(net.get_score())]
        # Adam's first moment after one step is (1 - beta1) * g
        grad_norms = self._named(
            program.leaf_norms(program.optimizer_field(net.opt_state, "mu")),
            scale=1.0 / (1.0 - cfg["optimizer"]["beta1"]))
        self.program = {"losses": losses, "grad_norms": grad_norms,
                        "delta_norms": self._named(
                            self._delta_norms(net.params))}
        gc.collect()
        n_params = sum(int(np.prod(a.shape))
                       for a in jax.tree_util.tree_leaves(net.params))
        common.say(f"byte_fit_stream: {n_params / 1e6:.2f} M parameters; "
                   f"the program built its model in {t_built - t0:.1f} s, "
                   f"weights from the seed {t_weights - t_built:.1f} s, "
                   "first step (compile or cache load, and its readings) "
                   f"{time.perf_counter() - t_weights:.1f} s; loss "
                   f"{losses[0]:.4f}")

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
        nbytes = self.cell["rows"] * self.cfg["train_seq_len"]
        common.say(f"byte_fit_stream: {steps} steps in {seconds:.3f} s, "
                   f"{steps * nbytes / seconds:.1f} bytes/s, "
                   f"last loss {float(net.get_score()):.4f}; longest waits "
                   f"for a batch (s, before batch) {stream.longest_waits()}")
        metrics = {"train_step_ms": 1e3 * seconds / max(steps, 1)}
        if t_start is not None:
            # process start to the first timed dispatch
            metrics["setup_s"] = t0 - t_start
        return {"steps": steps, "attempted": steps, "failed": failed,
                "metrics": metrics}

    def window(self, seconds: float, t_start: float):
        return self._run(TimedStream(self.batches, self.net,
                                     seconds=seconds), t_start)

    def traced_stretch(self):
        return self._run(TimedStream(self.batches, self.net,
                                     count=self.cell["trace_steps"]))

    # --------------------------------------------------------------- after
    def release(self):
        program.free(self.net)
        self.net = None
        gc.collect()

    def checked_batches(self):
        """The batch of the first step, which the reference follows."""
        return self.batches[:1]

    def reference(self, batches, precision="float32", keep_rows=None,
                  fault=None):
        """``keep_rows`` is the fault ``tools/readings.py`` plants: the
        rows it keeps, and of a batch of one row, which cannot lose one,
        the targets of the first half of the positions."""
        from benchmark.reference import evabyte as ref
        pairs = [(b[0], b[1]) for b in batches]
        if keep_rows is not None:
            if len(keep_rows) < self.cell["rows"]:
                pairs = [(x[keep_rows], y[keep_rows]) for x, y in pairs]
            else:
                fault = "half_targets"
        return ref.train_steps(self.cfg, common.seed_key(self.seed), pairs,
                               precision, fault)

    def check(self):
        """Run once the window has closed and the program's state is freed."""
        extra = {}
        want = self.cell.get("require_kernels")
        if want:
            have = kernels_in_timed_program(self)
            missing = [k for k in want if k not in have]
            extra["kernels_missing"] = (len(missing), 0, not missing)
            if missing:
                common.say(f"byte_fit_stream: the timed train step lacks "
                           f"{missing}")
        self.release()
        read = check_train.readings(self.program,
                                    self.reference(self.checked_batches()))
        common.say(f"byte_fit_stream: worst leaves {read['_where']}")
        return check_train.verdict(read, self.cell.get("limits", {}), extra)

    def flops_per_step(self, flops_module):
        return flops_module.train_step_flops(self.cfg, self.cell["rows"])
