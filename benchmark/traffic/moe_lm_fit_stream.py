"""Traffic kind ``moe_lm_fit_stream``: next-token training of one chip's
share of a sparse mixture-of-experts decoder (the ``trinity`` family), fed
batch by batch as an iterator to ``MultiLayerNetwork.fit`` exactly as
``lm_fit_stream`` feeds its model (the feed is that kind's ``Stream``, the
batches its ``lm_batches``), and reporting the same ``train_step_ms`` and
``setup_s``.

The cell's file gives ``rows`` (sequences a step), ``distinct_batches``,
``check_steps`` (1: the reference keeps no Adam moments) and
``trace_steps``.  The configuration's file gives the sizes, the
``precision``, ``train_seq_len`` (the tokens in a sequence) and the share:
``num_experts`` experts held of ``published.num_experts`` routed over, a
``vocab_size`` that is a slice of the published one (ids are drawn from
the slice).

Beside ``check/train.py``'s three gaps the kind compares
``routing_agreement``: the share of (token, slot) choices of the first
step's batch, over all routed layers, on which the program and the
reference agree (a slot agrees where the program's ``k``-th choice of a
token is among the reference's choices for it).  The program's choices
come from its own layers (``TransformerBlock.routing`` beside ``apply``),
walked in set-up at the configuration's precision on the weights the first
step then starts from.

The state fills half the chip (12 bytes a parameter), so, as
``byte_fit_stream`` does, set-up never holds two copies of the weights:
the network's own initial weights are dropped before the benchmark's are
made, and the change of the parameters is measured against weights made
again from the seed inside the program that takes the norms.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from benchmark import common, program
from benchmark.check import train as check_train

_lm = common.load_module("traffic", "lm_fit_stream")
_byte = common.load_module("traffic", "byte_fit_stream")
Stream, lm_batches = _lm.Stream, _lm.lm_batches
TimedStream = _byte.TimedStream
kernels_in_timed_program = _lm.Job.kernels_in_timed_program

# the program's name for a block's leaf -> the reference's
BLOCK_LEAVES = {"mha_Wq": "Wq", "mha_Wk": "Wk", "mha_Wv": "Wv",
                "mha_Wo": "Wo", "mha_Wg": "Wgate", "mha_q_norm": "q_norm",
                "mha_k_norm": "k_norm", "ln1_g": "n1", "ln1p_g": "n2",
                "ln2_g": "n3", "ln2p_g": "n4", "Wg": "Wg", "W1": "W1",
                "W2": "W2", "router": "router", "wg": "eg", "w1": "e1",
                "w2": "e2", "sg": "sg", "s1": "s1", "s2": "s2"}
# the program stores a norm's gain as an offset from one
GAINS = ("mha_q_norm", "mha_k_norm", "ln1_g", "ln1p_g", "ln2_g", "ln2p_g")


def reference_name(n_layer: int, layer: str, leaf: str) -> str:
    """The reference's name for the program's ``params[layer][leaf]``: the
    embedding, ``n_layer`` blocks, the final norm, the head."""
    i = int(layer.split("_")[1])
    if i == 0:
        return "wte"
    if i == n_layer + 1:
        return "norm_w"
    if i == n_layer + 2:
        return "head_W"
    return f"layers.{i - 1}.{BLOCK_LEAVES[leaf]}"


def as_program(p: dict) -> dict:
    """The reference's tree in the program's layout."""
    n = len(p["layers"])
    out = {"layer_0": {"W": p["wte"]},
           f"layer_{n + 1}": {"gain": p["norm_w"] - 1.0},
           f"layer_{n + 2}": {"W": p["head_W"]}}
    for i, layer in enumerate(p["layers"]):
        out[f"layer_{i + 1}"] = {
            mine: layer[theirs] - (1.0 if mine in GAINS else 0.0)
            for mine, theirs in BLOCK_LEAVES.items() if theirs in layer}
    return out


def build(cfg: dict, seq_len: int = None):
    """The program's model from the configuration's keys."""
    from deeplearning4j_tpu.models import TrinityLM
    compute = None if cfg["precision"] == "float32" else cfg["precision"]
    return TrinityLM(
        vocab_size=cfg["vocab_size"],
        seq_len=seq_len or cfg["train_seq_len"], embed=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn_hidden=cfg["intermediate_size"],
        moe_hidden=cfg["moe_intermediate_size"],
        experts=cfg["published"]["num_experts"],
        experts_held=tuple(cfg["experts_held"]),
        top_k=cfg["num_experts_per_tok"],
        shared_experts=cfg["num_shared_experts"],
        route_scale=cfg["route_scale"],
        dense_layers=cfg["num_dense_layers"],
        layer_types=tuple(cfg["layer_types"]),
        n_layers=cfg["num_hidden_layers"], window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        attn_impl="auto", cache_mode=cfg["cache_mode"],
        compute_dtype=compute, updater=program.updater(cfg)).init()


def program_choices(net, cfg: dict, x):
    """The experts every token of ``x [rows, t]`` chooses in every routed
    layer, ``[rows, layers, t, k]``, by the program's own layers at the
    configuration's precision: each block's ``routing`` beside its
    ``apply``, on the network's present weights."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["precision"])
    layers = net.conf.layers

    @jax.jit
    def walk(params, state, x):
        h, chosen = x, []
        for i, lc in enumerate(layers[:-1]):
            p = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                       params.get(f"layer_{i}", {}))
            variables = {"params": p, "state": state.get(f"layer_{i}", {})}
            if getattr(lc, "moe_top_k", 0):
                idx, _ = lc.routing(variables, h)
                chosen.append(idx.reshape(x.shape[0], x.shape[1], -1))
            h, _ = lc.apply(variables, h, train=True)
        return jnp.stack(chosen, axis=1)
    return np.asarray(walk(net.params, net.state, x))


def routing_agreement(mine, theirs) -> float:
    """Share of the program's (token, slot) choices that are among the
    reference's choices for that token and layer."""
    mine, theirs = np.asarray(mine), np.asarray(theirs)
    return float(np.mean((mine[..., :, None] == theirs[..., None, :])
                         .any(axis=-1)))


class Job:
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
        from benchmark.reference import trinity as ref
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
            raise ValueError("moe_lm_fit_stream follows one step: its "
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
        choices = program_choices(net, cfg, self.batches[0][0])
        t_routed = time.perf_counter()
        # the window's own call and feed, one step
        net.fit(Stream(self.batches[:1], net, count=1))
        losses = [float(net.get_score())]
        # Adam's first moment after one step is (1 - beta1) * g
        grad_norms = self._named(
            program.leaf_norms(program.optimizer_field(net.opt_state, "mu")),
            scale=1.0 / (1.0 - cfg["optimizer"]["beta1"]))
        tokens = {k: np.asarray(v["expert_tokens"]).tolist()
                  for k, v in net.state.items()
                  if isinstance(v, dict) and "expert_tokens" in v}
        self.program = {"losses": losses, "grad_norms": grad_norms,
                        "delta_norms": self._named(
                            self._delta_norms(net.params)),
                        "route_choices": choices, "expert_tokens": tokens}
        gc.collect()
        n_params = sum(int(np.prod(a.shape))
                       for a in jax.tree_util.tree_leaves(net.params))
        common.say(f"moe_lm_fit_stream: {n_params / 1e6:.2f} M parameters; "
                   f"the program built its model in {t_built - t0:.1f} s, "
                   f"weights from the seed {t_weights - t_built:.1f} s, its "
                   f"routing of the first batch {t_routed - t_weights:.1f} "
                   "s, first step (compile or cache load, and its "
                   f"readings) {time.perf_counter() - t_routed:.1f} s; loss "
                   f"{losses[0]:.4f}; pairs each held expert took in that "
                   f"step {tokens}")

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
        tokens = self.cell["rows"] * self.cfg["train_seq_len"]
        common.say(f"moe_lm_fit_stream: {steps} steps in {seconds:.3f} s, "
                   f"{steps * tokens / seconds:.1f} tokens/s, "
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
        """``keep_rows`` is how ``tools/readings.py`` asks for a fault: of a
        batch of one row, which cannot lose one, it plants ``no_window``."""
        from benchmark.reference import trinity as ref
        if keep_rows is not None and fault is None:
            fault = "no_window"
        return ref.train_steps(self.cfg, common.seed_key(self.seed),
                               [(b[0], b[1]) for b in batches], precision,
                               fault)

    def compare(self, program_side, reference_side, extra=None):
        """``(correct, compared, read)`` of two sides' readings under the
        cell's limits: ``check/train.py``'s gaps, and the routing's
        agreement, which is better higher: its limit is a floor."""
        limits = dict(self.cell.get("limits", {}))
        floor = limits.pop("routing_agreement", None)
        read = check_train.readings(program_side, reference_side)
        common.say(f"moe_lm_fit_stream: worst leaves {read['_where']}")
        read["routing_agreement"] = routing_agreement(
            program_side["route_choices"], reference_side["route_choices"])
        extra = dict(extra or {})
        if floor is not None:
            extra["routing_agreement"] = (
                read["routing_agreement"], floor,
                read["routing_agreement"] >= floor)
        return (*check_train.verdict(read, limits, extra), read)

    def check(self):
        """Run once the window has closed and the program's state is freed."""
        kernels = {}
        want = self.cell.get("require_kernels")
        if want:
            have = kernels_in_timed_program(self)
            missing = [k for k in want if k not in have]
            kernels["kernels_missing"] = (len(missing), 0, not missing)
            if missing:
                common.say(f"moe_lm_fit_stream: the timed train step lacks "
                           f"{missing}")
        self.release()
        return self.compare(self.program,
                            self.reference(self.checked_batches()),
                            kernels)[:2]

    def flops_per_step(self, flops_module):
        return flops_module.train_step_flops(self.cfg, self.cell["rows"])
