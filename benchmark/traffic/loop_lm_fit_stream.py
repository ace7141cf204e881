"""Traffic kind ``loop_lm_fit_stream``: next-token training of a looped
decoder (the ``ouro`` family: one run of layers walked ``total_ut_steps``
times on one set of weights, an exit gate after every pass, the
exit-weighted loss), fed batch by batch as an iterator to
``MultiLayerNetwork.fit`` exactly as ``lm_fit_stream`` feeds its model
(the batches are that kind's ``lm_batches``; the timed windows are
``byte_fit_stream``'s own: ``Job`` here extends that kind's, so their log
lines carry its name), and reporting the same ``train_step_ms`` and
``setup_s``.

The cell's file gives ``rows`` (sequences a step), ``distinct_batches``,
``check_steps`` (1: the reference keeps no Adam moments) and
``trace_steps``.  The configuration's file gives the sizes, the
``precision``, ``train_seq_len``, ``cache_mode`` and ``exit_beta``.  A
batch is ``(ids[:, :-1], ids[:, 1:])`` of rows of ``train_seq_len + 1``
token ids uniform on the vocabulary from the seed.

The comparison is ``check/train.py``'s three gaps and nothing else: the
exit gate's two leaves (``gate_w``, ``gate_b``) are leaves like any other,
and at the cell's size their gradient is no smaller than the median
leaf's, so a gate that gets none reads a ``grad_gap`` of 1.  The log prints
the exits' mean shares (``loop_exit_mass``) and what the looped run kept
(``loop_runs_traced_total``).

The state fills half the chip (12 bytes a parameter) and the step most of
the rest, so set-up never holds two copies of the weights (see
``byte_fit_stream``).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import common, program
from benchmark.check import train as check_train

_lm = common.load_module("traffic", "lm_fit_stream")
_byte = common.load_module("traffic", "byte_fit_stream")
Stream, lm_batches = _lm.Stream, _lm.lm_batches
kernels_in_timed_program = _lm.Job.kernels_in_timed_program

# the program's name for a block's leaf -> the reference's
BLOCK_LEAVES = {"mha_Wq": "Wq", "mha_Wk": "Wk", "mha_Wv": "Wv",
                "mha_Wo": "Wo", "Wg": "Wg", "W1": "W1", "W2": "W2",
                "ln1_g": "n1", "ln1p_g": "n2", "ln2_g": "n3", "ln2p_g": "n4"}
# the program stores a norm's gain as an offset from one
GAINS = ("ln1_g", "ln1p_g", "ln2_g", "ln2p_g")
HEAD_LEAVES = {"W": "head_W", "w_g": "gate_w", "b_g": "gate_b"}


def reference_name(n_layer: int, layer: str, leaf: str) -> str:
    """The reference's name for the program's ``params[layer][leaf]``: the
    embedding, ``n_layer`` blocks, the final norm, the head with its
    gate."""
    i = int(layer.split("_")[1])
    if i == 0:
        return "wte"
    if i == n_layer + 1:
        return "norm_w"
    if i == n_layer + 2:
        return HEAD_LEAVES[leaf]
    return f"layers.{i - 1}.{BLOCK_LEAVES[leaf]}"


def as_program(p: dict) -> dict:
    """The reference's tree in the program's layout."""
    n = len(p["layers"])
    out = {"layer_0": {"W": p["wte"]},
           f"layer_{n + 1}": {"gain": p["norm_w"] - 1.0},
           f"layer_{n + 2}": {mine: p[theirs]
                              for mine, theirs in HEAD_LEAVES.items()}}
    for i, layer in enumerate(p["layers"]):
        out[f"layer_{i + 1}"] = {
            mine: layer[theirs] - (1.0 if mine in GAINS else 0.0)
            for mine, theirs in BLOCK_LEAVES.items()}
    return out


def build(cfg: dict, seq_len: int = None):
    """The program's model from the configuration's keys."""
    from deeplearning4j_tpu.models import OuroLM
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("OuroLM has as many K/V heads as query heads")
    compute = None if cfg["precision"] == "float32" else cfg["precision"]
    return OuroLM(
        vocab_size=cfg["vocab_size"],
        seq_len=seq_len or cfg["train_seq_len"], embed=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        ffn_hidden=cfg["intermediate_size"], passes=cfg["total_ut_steps"],
        exit_beta=cfg["exit_beta"], rope_theta=float(cfg["rope_theta"]),
        eps=cfg["rms_norm_eps"], attn_impl="auto",
        cache_mode=cfg["cache_mode"], compute_dtype=compute,
        updater=program.updater(cfg)).init()


def loop_counters() -> dict:
    """What the program's registry says of the loop: the runs traced by
    what they saved, the bytes the last one stacks, the exits' mean shares
    in the last step read; ``{}`` of a program without them."""
    from deeplearning4j_tpu.observability.registry import default_registry
    reg, out = default_registry(), {}
    runs = reg.get("loop_runs_traced_total")
    if runs is not None:
        out["loop_runs_traced_total"] = {
            "/".join(labels): int(child.value)
            for labels, child in runs.samples()}
    stacks = reg.get("loop_saved_stack_bytes")
    if stacks is not None:
        out["loop_saved_stack_bytes"] = int(stacks.value)
    mass = reg.get("loop_exit_mass")
    if mass is not None:
        out["loop_exit_mass"] = [round(float(child.value), 4)
                                 for _labels, child in mass.samples()]
    return out


class Job(_byte.Job):
    """``byte_fit_stream``'s job with this family's model, batches,
    reference and names: the windows (``_run``, ``window``,
    ``traced_stretch``), ``release``, ``checked_batches`` and
    ``flops_per_step`` are that kind's own."""

    def __init__(self, cell: dict, cfg: dict, seed: int, devices):
        self.cell, self.cfg, self.seed, self.devices = cell, cfg, seed, devices
        self.net = None
        self.program = None          # its readings of the first step
        self.batches = lm_batches(seed, cell["distinct_batches"],
                                  cell["rows"], cfg["train_seq_len"],
                                  cfg["vocab_size"])

    # ------------------------------------------------------------- set-up
    def _seed_weights(self):
        """The benchmark's weights from the seed, as the reference holds
        them."""
        from benchmark.reference import ouro as ref
        return ref.init_params(self.cfg, common.seed_key(self.seed))

    def _named(self, norms: dict, scale=1.0) -> dict:
        n = self.cfg["num_hidden_layers"]
        return {reference_name(n, layer, leaf): norm * scale
                for (layer, leaf), norm in norms.items()}

    def _delta_norms(self, params) -> dict:
        """Norms of ``params`` minus the seed's weights, by the program's
        leaves; the seed's weights are laid out inside the one program, so
        no second copy in the program's layout is ever held."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def norms(now, seed_weights):
            then = as_program(seed_weights)
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
            raise ValueError("loop_lm_fit_stream follows one step: its "
                             "reference keeps no Adam moments")
        t0 = time.perf_counter()
        self.net = net = build(cfg)
        t_built = time.perf_counter()
        # the optimizer's state is zeros already; the network's own weights
        # go before the seed's come, so that the two never lie side by side
        empty = {k: v for k, v in net.params.items() if not v}
        net.params = None
        net.params = {**empty, **jax.jit(as_program)(self._seed_weights())}
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
        common.say(f"loop_lm_fit_stream: {n_params / 1e6:.2f} M parameters; "
                   f"the program built its model in {t_built - t0:.1f} s, "
                   f"weights from the seed {t_weights - t_built:.1f} s, "
                   "first step (compile or cache load, and its readings) "
                   f"{time.perf_counter() - t_weights:.1f} s; loss "
                   f"{losses[0]:.4f}; the loop by its counters "
                   f"{loop_counters()}")

    # --------------------------------------------------------------- after
    def reference(self, batches, precision="float32", keep_rows=None,
                  fault=None):
        """``keep_rows`` is how ``tools/readings.py`` asks for a fault: of
        a batch of one row, which cannot lose one, it plants
        ``passes_3``."""
        from benchmark.reference import ouro as ref
        if keep_rows is not None and fault is None:
            fault = "passes_3"
        return ref.train_steps(self.cfg, common.seed_key(self.seed),
                               batches, precision, fault)

    def compare(self, program_side, reference_side, extra=None):
        """``(correct, compared, read)`` of two sides' readings under the
        cell's limits (what ``tools/verdicts.py`` asks a kind for)."""
        read = check_train.readings(program_side, reference_side)
        common.say(f"loop_lm_fit_stream: worst leaves {read['_where']}")
        return (*check_train.verdict(read, self.cell.get("limits", {}),
                                     extra), read)

    def check(self):
        """Run once the window has closed and the program's state is freed."""
        kernels = {}
        want = self.cell.get("require_kernels")
        if want:
            have = kernels_in_timed_program(self)
            missing = [k for k in want if k not in have]
            kernels["kernels_missing"] = (len(missing), 0, not missing)
            if missing:
                common.say(f"loop_lm_fit_stream: the timed train step lacks "
                           f"{missing}")
        self.release()
        reference = self.reference(self.checked_batches())
        common.say(f"loop_lm_fit_stream: the reference's loss "
                   f"{reference['losses'][0]:.4f} = "
                   f"{reference['loss_parts']['expected']:.4f} - "
                   f"{self.cfg['exit_beta']} x "
                   f"{reference['loss_parts']['entropy']:.4f}; its exits' "
                   f"mean shares {reference['exit_mass']}; the program's "
                   "in the last step read "
                   f"{loop_counters().get('loop_exit_mass')}")
        return self.compare(self.program, reference, kernels)[:2]
