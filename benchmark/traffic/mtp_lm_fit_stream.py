"""Traffic kind ``mtp_lm_fit_stream``: training one chip's share of a
decoder with latent attention, routed experts and a multi-token-prediction
module (the ``joyai`` family), fed batch by batch as an iterator to
``ComputationGraph.fit``: the model is a graph with one input, the row of
``train_seq_len + 1`` token ids, and one output layer that scores two
streams (the trunk's next-token predictions and the module's
token-after-next ones), so a batch is ``([ids], [targets], None, [label
mask])`` and the step's loss ``L_main + mtp_loss_weight * L_mtp``.  The
feed is ``lm_fit_stream``'s ``Stream``, the ids its ``lm_batches``'
recipe; the timed windows, the comparison and the routing's agreement are
``moe_lm_fit_stream``'s own (``Job`` here extends that kind's); it reports
the same ``train_step_ms`` and ``setup_s``.

The cell's file gives ``rows`` (sequences a step), ``distinct_batches``,
``check_steps`` (1: the reference keeps no Adam moments) and
``trace_steps``.  The configuration's file gives the sizes, the
``precision``, ``train_seq_len`` and the share: ``n_routed_experts``
experts held of ``published.n_routed_experts`` routed over, a
``vocab_size`` that is a slice of the published one (ids are drawn from
the slice).

Beside ``check/train.py``'s three gaps the kind compares
``routing_agreement`` as ``moe_lm_fit_stream`` does, over the trunk's
routed layers and the module's: the program's choices come from its own
vertices (``TransformerBlock.routing`` beside ``apply``), walked in set-up
at the configuration's precision on the weights the first step then
starts from.  ``loss_gap`` is of the whole loss, both terms.

The state fills half the chip (12 bytes a parameter), so set-up never
holds two copies of the weights (see ``moe_lm_fit_stream``).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import common, program

_moe = common.load_module("traffic", "moe_lm_fit_stream")
Stream, routing_agreement = _moe.Stream, _moe.routing_agreement

# the program's name for a block's leaf -> the reference's
BLOCK_LEAVES = {"mha_Wqa": "Wqa", "mha_qa_norm": "qa_norm",
                "mha_Wqb": "Wqb", "mha_Wkva": "Wkva",
                "mha_kva_norm": "kva_norm", "mha_Wkvb": "Wkvb",
                "mha_Wo": "Wo", "ln1_g": "n1", "ln2_g": "n2", "Wg": "Wg",
                "W1": "W1", "W2": "W2", "router": "router", "wg": "eg",
                "w1": "e1", "w2": "e2", "sg": "sg", "s1": "s1", "s2": "s2"}
# the program stores a norm's gain as an offset from one
GAINS = ("mha_qa_norm", "mha_kva_norm", "ln1_g", "ln2_g")
MERGE_LEAVES = {"W": "Weh", "enorm": "enorm", "hnorm": "hnorm"}


def reference_name(vertex: str, leaf: str) -> str:
    """The reference's name for the program's ``params[vertex][leaf]``."""
    if vertex in ("embed", "norm", "head"):
        return {"embed": "wte", "norm": "norm_w", "head": "head_W"}[vertex]
    if vertex == "mtp_merge":
        return "mtp." + MERGE_LEAVES[leaf]
    if vertex == "mtp_norm":
        return "mtp.norm_w"
    if vertex == "mtp_block":
        return "mtp.block." + BLOCK_LEAVES[leaf]
    return f"layers.{int(vertex.split('_')[1])}.{BLOCK_LEAVES[leaf]}"


def _block(layer: dict) -> dict:
    return {mine: layer[theirs] - (1.0 if mine in GAINS else 0.0)
            for mine, theirs in BLOCK_LEAVES.items() if theirs in layer}


def as_program(p: dict) -> dict:
    """The reference's tree in the program's layout."""
    out = {"embed": {"W": p["wte"]}, "norm": {"gain": p["norm_w"] - 1.0},
           "head": {"W": p["head_W"]}}
    for i, layer in enumerate(p["layers"]):
        out[f"block_{i}"] = _block(layer)
    if "mtp" in p:
        m = p["mtp"]
        out.update(mtp_merge={"W": m["Weh"], "enorm": m["enorm"] - 1.0,
                              "hnorm": m["hnorm"] - 1.0},
                   mtp_block=_block(m["block"]),
                   mtp_norm={"gain": m["norm_w"] - 1.0})
    return out


def build(cfg: dict, seq_len: int = None):
    """The program's model from the configuration's keys."""
    from deeplearning4j_tpu.models import JoyAIFlashLM
    if cfg["num_nextn_predict_layers"] != 1:
        raise ValueError("JoyAIFlashLM has one multi-token-prediction "
                         "module")
    compute = None if cfg["precision"] == "float32" else cfg["precision"]
    return JoyAIFlashLM(
        vocab_size=cfg["vocab_size"],
        seq_len=seq_len or cfg["train_seq_len"], embed=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        ffn_hidden=cfg["intermediate_size"],
        moe_hidden=cfg["moe_intermediate_size"],
        experts=cfg["published"]["n_routed_experts"],
        experts_held=tuple(cfg["experts_held"]),
        top_k=cfg["num_experts_per_tok"],
        shared_experts=cfg["n_shared_experts"],
        route_scale=cfg["routed_scaling_factor"],
        dense_layers=cfg["first_k_dense_replace"],
        n_layers=cfg["num_hidden_layers"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        attn_impl="auto", cache_mode=cfg["cache_mode"],
        compute_dtype=compute, updater=program.updater(cfg)).init()


def mtp_batches(seed: int, n_batches: int, rows: int, seq_len: int,
                vocab: int, mtp_weight: float):
    """``([ids], [targets], None, [label mask])`` batches of rows of
    ``seq_len + 1`` token ids, uniform over the slice, drawn as
    ``lm_batches`` draws them; targets and mask by the program's own
    ``JoyAIFlashLM.batch``."""
    from deeplearning4j_tpu.models import JoyAIFlashLM
    rng = np.random.default_rng(int(seed))
    ids = rng.integers(0, vocab, (n_batches, rows, seq_len + 1)).astype(
        np.int32)
    return [JoyAIFlashLM.batch(b, mtp_weight) for b in ids]


def program_choices(net, cfg: dict, ids):
    """The experts every token of the rows ``ids [rows, t + 1]`` chooses in
    every routed block, the trunk's and then the module's, ``[rows,
    blocks, t, k]``, by the program's own vertices at the configuration's
    precision: each block's ``routing`` beside its ``apply``, on the
    network's present weights."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["precision"])
    conf = net.conf

    @jax.jit
    def walk(params, state, ids):
        acts, chosen = {conf.network_inputs[0]: ids}, []
        for name in conf.topological_order:
            if name in conf.network_outputs:
                continue
            v = conf.vertices[name]
            xs = [acts[src] for src in conf.vertex_inputs[name]]
            p = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                       params.get(name, {}))
            variables = {"params": p, "state": state.get(name, {})}
            lc = getattr(v, "layer", None)
            if getattr(lc, "moe_top_k", 0):
                idx, _ = lc.routing(variables, xs[0])
                chosen.append(idx.reshape(ids.shape[0], ids.shape[1] - 1,
                                          -1))
            acts[name], _ = v.apply(variables, xs, train=True)
        return jnp.stack(chosen, axis=1)
    return np.asarray(walk(net.params, net.state, ids))


def kernels_in_timed_program(rows: int):
    """Names of the program's attention kernels in the lowered train step
    that ran on this cell's batches (``lm_fit_stream``'s check, for a
    graph: its batch arguments are lists)."""
    from deeplearning4j_tpu.nn import compile_cache
    from deeplearning4j_tpu.ops.flash_attention import KERNEL_NAMES
    found = set()
    for _key, entry in compile_cache.iter_trace_cache():
        if entry.name != "train_step":
            continue
        for spec in entry.audit_specs():
            xs = spec[0][4]
            if not isinstance(xs, (list, tuple)) or \
                    xs[0].shape[0] != rows:
                continue
            text = entry.audit_lower(spec).as_text()
            if "tpu_custom_call" in text:
                found.update(n for n in KERNEL_NAMES if n in text)
    return sorted(found)


def traced_counters() -> dict:
    """The trace-time counters of what this family adds, summed over
    their labels."""
    from deeplearning4j_tpu.observability.registry import default_registry
    out = {}
    for name in ("mla_layers_traced_total", "mtp_modules_traced_total",
                 "moe_layers_traced_total"):
        inst = default_registry().get(name)
        out[name] = 0 if inst is None else int(sum(
            child.value for _labels, child in inst.samples()))
    return out


class Job(_moe.Job):
    """``moe_lm_fit_stream``'s job with this family's model, batches,
    reference and names: the windows (``_run``, ``window``,
    ``traced_stretch``), ``release``, ``compare`` (the three gaps and the
    routing's agreement under the cell's limits) and ``flops_per_step``
    are that kind's own, so their log lines carry its name."""

    def __init__(self, cell: dict, cfg: dict, seed: int, devices):
        self.cell, self.cfg, self.seed, self.devices = cell, cfg, seed, devices
        self.net = None
        self.program = None          # its readings of the first step
        self.batches = mtp_batches(seed, cell["distinct_batches"],
                                   cell["rows"], cfg["train_seq_len"],
                                   cfg["vocab_size"], cfg["mtp_loss_weight"])

    # ------------------------------------------------------------- set-up
    def _seed_weights(self):
        """The benchmark's weights from the seed, as the reference holds
        them."""
        from benchmark.reference import joyai as ref
        return ref.init_params(self.cfg, common.seed_key(self.seed))

    @staticmethod
    def _named(norms: dict, scale=1.0) -> dict:
        return {reference_name(vertex, leaf): norm * scale
                for (vertex, leaf), norm in norms.items()}

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
            raise ValueError("mtp_lm_fit_stream follows one step: its "
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
        choices = program_choices(net, cfg, self.batches[0][0][0])
        t_routed = time.perf_counter()
        before = traced_counters()
        # the window's own call and feed, one step
        net.fit(Stream(self.batches[:1], net, count=1))
        losses = [float(net.get_score())]
        traced = {k: v - before[k] for k, v in traced_counters().items()}
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
        common.say(f"mtp_lm_fit_stream: {n_params / 1e6:.2f} M parameters; "
                   f"the program built its graph in {t_built - t0:.1f} s, "
                   f"weights from the seed {t_weights - t_built:.1f} s, its "
                   f"routing of the first batch {t_routed - t_weights:.1f} "
                   "s, first step (compile or cache load, and its "
                   f"readings) {time.perf_counter() - t_routed:.1f} s; loss "
                   f"{losses[0]:.4f} (both terms); traced into that step "
                   f"{traced}; pairs each held expert took in it {tokens}")

    # --------------------------------------------------------------- after
    def checked_batches(self):
        """The rows of ids of the first step, which the reference
        follows."""
        return [self.batches[0][0][0]]

    def reference(self, batches, precision="float32", keep_rows=None,
                  fault=None):
        """``keep_rows`` is how ``tools/readings.py`` asks for a fault: of
        a batch of one row, which cannot lose one, it plants
        ``no_k_rope``."""
        from benchmark.reference import joyai as ref
        if keep_rows is not None and fault is None:
            fault = "no_k_rope"
        return ref.train_steps(self.cfg, common.seed_key(self.seed),
                               batches, precision, fault)

    def check(self):
        """Run once the window has closed and the program's state is freed."""
        kernels = {}
        want = self.cell.get("require_kernels")
        if want:
            have = kernels_in_timed_program(self.cell["rows"])
            missing = [k for k in want if k not in have]
            kernels["kernels_missing"] = (len(missing), 0, not missing)
            if missing:
                common.say(f"mtp_lm_fit_stream: the timed train step lacks "
                           f"{missing}")
        over = {k: int(v["expert_overflows"])
                for k, v in self.net.state.items()
                if isinstance(v, dict) and "expert_overflows" in v}
        common.say("mtp_lm_fit_stream: steps so far whose routing sent a "
                   f"layer's held experts more pairs than its buffers hold "
                   f"{over}")
        self.release()
        reference = self.reference(self.checked_batches())
        common.say(f"mtp_lm_fit_stream: the reference's loss "
                   f"{reference['losses'][0]:.4f} = "
                   f"{reference['loss_parts']['main']:.4f} + "
                   f"{self.cfg['mtp_loss_weight']} x "
                   f"{reference['loss_parts']['mtp']:.4f}")
        return self.compare(self.program, reference, kernels)[:2]
