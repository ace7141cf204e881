"""The canonical program set: the manifest the CI gate audits.

One tiny representative per steady-state program class the framework
ships — dense / ZeRO-3-sharded (dp=2, dp=4) / bf16 train steps, the
serving forward, and the generation programs (the paged-KV
prefill/decode pair) — driven through the
REAL production entry points (``fit``, ``ShardedTrainer.fit``, the
``serve`` jit, ``GenerationEngine.warmup``), so the audited jaxprs are
the very traces production executes, not hand-built fixtures.  The
dense and sharded runs deliberately share one topology: they exercise
the PR-12 contract that sharding lives in the ARGUMENTS (one trace,
three recorded specs at mesh sizes 1/2/4).

Suppressions declared here are the manifest's inline pragmas — each
with its mandatory justification, right next to the programs they
cover.  They are added CONDITIONALLY (the CPU-only donation skips exist
only on the CPU backend), so on a backend where the finding cannot
fire, the allowance is never declared and can never go stale-but-armed.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .audit import AuditConfig, AuditProgram, Suppression

__all__ = ["CANONICAL_CONFIG", "CanonicalSet", "build_canonical",
           "CANONICAL_PROGRAM_NAMES", "BUDGETS_PATH", "CARDS_DIR"]

#: the checked-in per-program IR budgets (AX008 + the --diff-cards
#: gate) and the committed card directory (AX010)
BUDGETS_PATH = os.path.join(os.path.dirname(__file__), "budgets.json")
CARDS_DIR = os.path.join(os.path.dirname(__file__), "cards")


def _peak_budgets() -> Optional[Dict[str, int]]:
    """``peak_live_bytes`` ceilings from budgets.json (AX008's input);
    None (rule disabled) when the file is absent/unreadable — the
    --diff-cards gate separately refuses to run without budgets, so a
    deleted budgets file cannot silently green the gate."""
    try:
        with open(BUDGETS_PATH, "r", encoding="utf-8") as fh:
            data = json.load(fh)
        return {name: int(row["peak_live_bytes"])
                for name, row in data.get("programs", {}).items()
                if row.get("peak_live_bytes") is not None}
    except (OSError, ValueError, KeyError, TypeError):
        return None


#: the canonical set audits TOY programs, so the donation-threshold
#: teeth come from a low floor (the serve batch is ~512 bytes; at the
#: default 1 MiB nothing toy-sized would ever exercise AX005/AX007);
#: the committed budgets/cards arm AX008/AX010 on every canonical audit
CANONICAL_CONFIG = AuditConfig(min_donate_bytes=256,
                               peak_live_budgets=_peak_budgets(),
                               cards_dir=CARDS_DIR)

CANONICAL_PROGRAM_NAMES = (
    "train_step[dense]", "train_step[zero3,dp=2]", "train_step[zero3,dp=4]",
    "train_step[bf16]", "train_step[f16]", "serve",
    "paged_prefill", "paged_decode", "train_step[embedding_zero3]",
)

_FEATURES, _CLASSES, _HIDDEN, _BATCH = 16, 8, 32, 8
#: the sparse-embedding canonical program's table: big enough that a
#: dense [vocab, dim] collective would dwarf every legitimate
#: touched-rows block (the no-dense-exchange pin in tests/test_audit.py)
EMBED_VOCAB, EMBED_DIM = 256, 8


def _mlp(precision: Optional[str] = None, seed: int = 19):
    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.updaters import Adam
    from deeplearning4j_tpu.nn.layers.feedforward import (DenseLayer,
                                                          OutputLayer)

    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater(Adam(learning_rate=0.02)))
    if precision is not None:
        b = b.precision(precision)
    lb = b.list()
    lb.layer(DenseLayer(n_out=_HIDDEN, activation="tanh"))
    lb.layer(OutputLayer(n_out=_CLASSES, activation="softmax",
                         loss="mcxent"))
    conf = lb.set_input_type(InputType.feed_forward(_FEATURES)).build()
    return MultiLayerNetwork(conf).init()


def _batch(n: int = _BATCH, seed: int = 0):
    import numpy as np

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, _FEATURES)).astype(np.float32)
    y = np.eye(_CLASSES, dtype=np.float32)[
        rng.integers(0, _CLASSES, n)]
    return x, y


def _spec_mesh_size(spec) -> int:
    import jax

    size = 1
    for leaf in jax.tree_util.tree_leaves(spec):
        mesh = getattr(getattr(leaf, "sharding", None), "mesh", None)
        if mesh is not None:
            size = max(size, int(mesh.size))
    return size


def _pick_spec(entry, mesh_size: int):
    """Newest recorded spec whose largest mesh is ``mesh_size``."""
    for spec in reversed(entry.audit_specs()):
        if _spec_mesh_size(spec) == mesh_size:
            return spec
    raise LookupError(
        f"no recorded spec of {entry.name} at mesh size {mesh_size} "
        f"(have {[_spec_mesh_size(s) for s in entry.audit_specs()]})")


def _pick_largest_prefill(entry):
    """The top-bucket prefill variant (tokens arg has the widest T)."""
    best, best_t = None, -1
    for spec in entry.audit_specs():
        args, _ = spec
        tokens = args[2]
        t = int(getattr(tokens, "shape", (0, 0))[1])
        if t > best_t:
            best, best_t = spec, t
    if best is None:
        raise LookupError("no prefill spec recorded")
    return best


@dataclass
class CanonicalSet:
    """The built canonical set, with its coverage made EXPLICIT: a
    wanted program this host could not build lands in ``skipped`` with
    the reason — consumers (CLI card pruning, the tests) must never
    mistake reduced coverage for the full set."""
    programs: List[AuditProgram]
    suppressions: List[Suppression]
    skipped: Dict[str, str] = field(default_factory=dict)


def build_canonical(include: Optional[Sequence[str]] = None,
                    dps: Tuple[int, ...] = (2, 4)) -> CanonicalSet:
    """Build (driving real fits/serves/generates) the canonical program
    set plus its manifest suppressions.

    ``include``: optional substrings — only programs whose name contains
    one are built (the golden-census test builds just the zero3 pair).
    Sharded programs are skipped (not errored) when the backend exposes
    fewer devices than ``dp``; generation programs when the model /
    generation extras are unavailable — each skip is recorded in
    ``CanonicalSet.skipped`` with its reason.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import compile_cache as cc

    def want(name: str) -> bool:
        return include is None or any(s in name for s in include)

    want_dense = want("train_step[dense]") or want("serve")
    want_sharded = [dp for dp in dps if want(f"train_step[zero3,dp={dp}]")]
    programs: List[AuditProgram] = []
    sups: List[Suppression] = []
    skipped: Dict[str, str] = {}
    cpu = jax.default_backend() == "cpu"
    prev_mode = cc.audit_capture_mode()
    cc.set_audit_capture("all")
    try:
        x, y = _batch()
        if want_dense or want_sharded:
            # ONE topology for dense + every dp: the sharded specs are
            # extra recorded layouts of the same single trace
            net = _mlp()
            entry = None
            if want_dense:
                net.fit(x, y)
                entry = net._get_jitted("train_step")
            if want("train_step[dense]"):
                programs.append(AuditProgram(
                    "train_step[dense]", entry, _pick_spec(entry, 1)))
            for dp in want_sharded:
                if len(jax.devices()) < dp:
                    skipped[f"train_step[zero3,dp={dp}]"] = \
                        f"needs >= {dp} devices, have {len(jax.devices())}"
                    continue
                from deeplearning4j_tpu.parallel import (ShardedTrainer,
                                                         make_mesh)
                net_s = _mlp()
                st = ShardedTrainer(net_s, make_mesh(dp=dp),
                                    min_shard_size=0)
                st.fit(x, y)
                entry = net_s._get_jitted("train_step")
                programs.append(AuditProgram(
                    f"train_step[zero3,dp={dp}]", entry,
                    _pick_spec(entry, dp), zero3=True))
            if want("serve"):
                serve = net._get_jitted("serve")
                serve(net.params, net.state, jnp.asarray(x))
                programs.append(AuditProgram(
                    "serve", serve, _pick_spec(serve, 1)))
                if cpu:
                    sups.append(Suppression(
                        "serve", "AX005",
                        "CPU implements no buffer donation; the serve "
                        "builder deliberately skips donate_argnums there "
                        "(nn/multilayer._build_stack_fn 'serve' branch) — "
                        "on TPU the padded batch IS donated"))
        if want("train_step[embedding_zero3]"):
            # the first structurally-sparse parameter: a sparse_grad
            # embedding table row-sharded over dp=2 — the program whose
            # card pins that NO collective carries O(vocab·dim) bytes
            # (the densified touched-rows exchange, arxiv 1905.04035,
            # derived by GSPMD from the zero3 argument shardings)
            if len(jax.devices()) < 2:
                skipped["train_step[embedding_zero3]"] = \
                    f"needs >= 2 devices, have {len(jax.devices())}"
            else:
                import numpy as np

                from deeplearning4j_tpu import (InputType,
                                                MultiLayerNetwork,
                                                NeuralNetConfiguration)
                from deeplearning4j_tpu.nn.conf.updaters import Adam
                from deeplearning4j_tpu.nn.layers.feedforward import (
                    EmbeddingLayer, OutputLayer)
                from deeplearning4j_tpu.parallel import (ShardedTrainer,
                                                         make_mesh)

                lb = (NeuralNetConfiguration.builder().seed(23)
                      .updater(Adam(learning_rate=0.02)).list())
                lb.layer(EmbeddingLayer(n_in=EMBED_VOCAB, n_out=EMBED_DIM,
                                        sparse_grad=True))
                lb.layer(OutputLayer(n_out=_CLASSES,
                                     activation="softmax", loss="mcxent"))
                net_e = MultiLayerNetwork(lb.build()).init()
                rng = np.random.default_rng(7)
                ids = rng.integers(0, EMBED_VOCAB,
                                   (_BATCH, 1)).astype(np.int32)
                ye = np.eye(_CLASSES, dtype=np.float32)[
                    rng.integers(0, _CLASSES, _BATCH)]
                st_e = ShardedTrainer(net_e, make_mesh(dp=2),
                                      min_shard_size=0)
                st_e.fit(ids, ye)
                entry_e = net_e._get_jitted("train_step")
                programs.append(AuditProgram(
                    "train_step[embedding_zero3]", entry_e,
                    _pick_spec(entry_e, 2), zero3=True))
        # the two low-precision variants: bf16 (no scaling) and f16
        # (dynamic loss scaling — its traced unscale/overflow-skip path
        # is where cast churn would live)
        for prec in ("bfloat16", "float16"):
            name = f"train_step[{'bf16' if prec == 'bfloat16' else 'f16'}]"
            if not want(name):
                continue
            net_p = _mlp(precision=prec)
            net_p.fit(x, y)
            entry_p = net_p._get_jitted("train_step")
            programs.append(AuditProgram(
                name, entry_p, _pick_spec(entry_p, 1), policy=prec))
        gen_names = ("paged_prefill", "paged_decode")
        if any(want(n) for n in gen_names):
            try:
                from deeplearning4j_tpu.generation import (
                    GenerationConfig, GenerationEngine)
                from deeplearning4j_tpu.models import TransformerLM
            except ImportError as e:
                for name in gen_names:
                    if want(name):
                        skipped[name] = \
                            f"generation/model extras unavailable: {e}"
                return CanonicalSet(programs, sups, skipped)

            lm = TransformerLM(vocab_size=17, seq_len=16, embed=16,
                               n_layers=2, n_heads=2).init()
            if want("paged_prefill") or want("paged_decode"):
                eng_p = GenerationEngine.for_model(
                    lm, GenerationConfig(max_slots=2, max_seq=16,
                                         block_size=4))
                try:
                    eng_p.warmup()
                    eng_p.generate([3, 1, 4], max_new_tokens=2)
                finally:
                    eng_p.shutdown()
            if want("paged_prefill"):
                ppf = lm._get_jitted("paged_prefill")
                programs.append(AuditProgram(
                    "paged_prefill", ppf, _pick_largest_prefill(ppf)))
                if cpu:
                    sups.append(Suppression(
                        "paged_prefill", "AX005",
                        "CPU implements no buffer donation; "
                        "generation/programs.build_generation_fn skips "
                        "donating the block pool there — on TPU both "
                        "paged programs donate it"))
                    sups.append(Suppression(
                        "paged_prefill", "AX007",
                        "same CPU no-donation skip, exact-solver form: "
                        "the lifetime solver proves the threaded block "
                        "pool (arg 4) donatable, and on TPU it IS "
                        "donated — CPU cannot alias buffers"))
            if want("paged_decode"):
                pdec = lm._get_jitted("paged_decode")
                programs.append(AuditProgram(
                    "paged_decode", pdec, pdec.audit_specs()[-1]))
                if cpu:
                    sups.append(Suppression(
                        "paged_decode", "AX005",
                        "CPU implements no buffer donation; "
                        "generation/programs.build_generation_fn skips "
                        "donating the block pool there — on TPU both "
                        "paged programs donate it"))
                    sups.append(Suppression(
                        "paged_decode", "AX007",
                        "same CPU no-donation skip, exact-solver form: "
                        "the lifetime solver proves the threaded block "
                        "pool (arg 3) donatable, and on TPU it IS "
                        "donated — CPU cannot alias buffers"))
    finally:
        cc.set_audit_capture(prev_mode)
    return CanonicalSet(programs, sups, skipped)
