"""Reusable benchmark configs mirroring BASELINE.md's table (LeNet-MNIST
step time, GravesLSTM char-RNN step time, Word2Vec words/sec).  The driver's
headline ResNet50 metric lives in ``bench.py``; these side metrics are
invoked from there (DL4J_TPU_BENCH_SIDE=1) and from ``tools/``.

All timings are steady-state — the compile-dominated first iteration is
always excluded (warm-up fit before any clock starts; ``_cold_steady_fit``
reports the compile-inclusive number separately as ``cold``) — and close on
a forced device→host fetch — block_until_ready alone can return early
through buffer-proxying transports (BENCH_NOTES round 1).  Training rows
time the device-resident epoch scan (``_scan_step_ms``), the path the
framework actually trains through.  Clocks come from the same monotonic
helpers the tracer/metrics tier uses (``observability.clock``), so bench
rows and span histograms are directly comparable.
"""
from __future__ import annotations

import os
import time
from typing import Dict, List, Optional

import numpy as np

from ..observability.clock import monotonic_s

_ENV_FINGERPRINT: Optional[Dict] = None


def env_fingerprint(refresh: bool = False) -> Dict:
    """Host/runtime provenance block stamped onto every bench JSON row
    (ISSUE 17 satellite): round-over-round comparisons keep mis-blaming
    the framework for environment drift (host load, jaxlib bumps), so
    every row carries the facts
    needed to rule that out.  Captured ONCE per process (load average is
    the *at-start* reading — a capture's own load must not pollute the
    rows it stamps); ``refresh=True`` re-reads for tests."""
    global _ENV_FINGERPRINT
    if _ENV_FINGERPRINT is not None and not refresh:
        return _ENV_FINGERPRINT
    import sys
    env: Dict = {
        "cpus": os.cpu_count(),
        "python": sys.version.split()[0],
    }
    try:
        env["loadavg_1m"] = round(os.getloadavg()[0], 2)
    except OSError:
        env["loadavg_1m"] = None
    try:
        import jax
        import jaxlib
        env["jax"] = jax.__version__
        env["jaxlib"] = jaxlib.__version__
        env["x64"] = bool(jax.config.jax_enable_x64)
    except Exception:
        env["jax"] = env["jaxlib"] = None
        env["x64"] = None
    # the knobs that change what a row measures: every DL4J_TPU_* override
    # in effect (values are short flags/paths, never secrets)
    env["overrides"] = {k: os.environ[k] for k in sorted(os.environ)
                        if k.startswith("DL4J_TPU_")}
    _ENV_FINGERPRINT = env
    return env


def _scan_step_ms(model, x, y, batch: int, nbatch: int, epochs: int = 2,
                  blocks: int = 3) -> float:
    """Per-step ms through the device-resident epoch scan (fit_on_device:
    one dispatch per epoch), so the row is not bound by per-step host
    dispatch."""
    model.fit_on_device(x, y, batch_size=batch, epochs=1)   # compile+warm
    steps = nbatch * epochs
    times = []
    for _ in range(blocks):
        t0 = monotonic_s()
        model.fit_on_device(x, y, batch_size=batch, epochs=epochs)
        times.append((monotonic_s() - t0) / steps * 1e3)
    return float(np.median(times))


def lenet_step_time(batch: int = 128, nbatch: int = 50) -> Dict:
    """LeNet-MNIST training step time (zoo ``model/LeNet.java:35``)."""
    import jax.numpy as jnp

    from ..models import LeNet
    model = LeNet().init()
    rng = np.random.default_rng(0)
    n = batch * nbatch
    x = jnp.asarray(rng.standard_normal((n, 28, 28, 1), dtype=np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, n)])
    ms = _scan_step_ms(model, x, y, batch, nbatch)
    return {"metric": "lenet_mnist_step_ms", "value": round(ms, 3),
            "unit": "ms/step", "batch": batch,
            "examples_per_sec": round(batch / ms * 1e3, 1)}


def char_lstm_step_time(batch: int = 128, timesteps: int = 64,
                        nbatch: int = 30) -> Dict:
    """Char-RNN step time (zoo ``model/TextGenerationLSTM.java:34``; the
    reference's cuDNN LSTM path, ``GravesLSTM.java:46``)."""
    import jax.numpy as jnp

    from ..models import TextGenerationLSTM
    model = TextGenerationLSTM(timesteps=timesteps).init()
    rng = np.random.default_rng(0)
    vocab = 26
    n = batch * nbatch
    x = jnp.asarray(np.eye(vocab, dtype=np.float32)[
        rng.integers(0, vocab, (n, timesteps))])
    y = jnp.asarray(np.eye(vocab, dtype=np.float32)[
        rng.integers(0, vocab, (n, timesteps))])
    ms = _scan_step_ms(model, x, y, batch, nbatch)
    return {"metric": "char_lstm_step_ms", "value": round(ms, 3),
            "unit": "ms/step", "batch": batch, "timesteps": timesteps,
            "tokens_per_sec": round(batch * timesteps / ms * 1e3, 1)}


def _zipf_sentences(vocab: int, n_sent: int, sent_len: int):
    """Zipf(1.3)-distributed synthetic corpus shared by the embedding
    benchmarks, so word2vec and PV rows measure the same token stream."""
    rng = np.random.default_rng(0)
    ids = np.clip(rng.zipf(1.3, size=n_sent * sent_len), 1, vocab) - 1
    toks = ["w%d" % i for i in ids]
    return [" ".join(toks[i * sent_len:(i + 1) * sent_len])
            for i in range(n_sent)]


def _cold_steady_fit(model, total_words: int, runs: int = 3):
    """(cold, steady) words/sec: first fit compiles; steady is the MEDIAN
    of ``runs`` reset-weights re-fits — these benches are dispatch/host
    bound, so a single timed fit is not a stable artifact.

    Every clock here closes on a HOST FETCH of the trained table
    (``_sync_tables``), and the queue is drained before each clock starts.
    ``fit()`` itself only enqueues async dispatches, so timing ``fit()``
    alone measures ENQUEUE rate, not training throughput."""
    def _sync_tables():
        float(np.asarray(model.lookup_table.syn0[0, 0]))

    model.build_vocab()
    t0 = monotonic_s()
    model.fit()
    _sync_tables()
    cold = total_words / (monotonic_s() - t0)
    rates = []
    for _ in range(runs):
        model.lookup_table.reset_weights()
        _sync_tables()                    # drain before starting the clock
        t0 = monotonic_s()
        model.fit()
        _sync_tables()
        rates.append(total_words / (monotonic_s() - t0))
    return cold, float(np.median(rates))


def word2vec_words_per_sec(vocab: int = 5000, n_sent: int = 20000,
                           sent_len: int = 20, epochs: int = 1) -> Dict:
    """Skip-gram NS throughput (parity bar: the reference's native batched
    AggregateSkipGram hot loop, ``SkipGram.java:271-283``)."""
    from ..nlp.word2vec import Word2Vec

    sentences = _zipf_sentences(vocab, n_sent, sent_len)
    total = n_sent * sent_len * epochs
    w2v = Word2Vec(sentences=sentences, layer_size=128, window=5, negative=5,
                   epochs=epochs, seed=1, min_word_frequency=1)
    cold, steady = _cold_steady_fit(w2v, total)
    return {"metric": "word2vec_words_per_sec", "value": round(steady, 1),
            "unit": "words/sec", "cold_words_per_sec": round(cold, 1),
            "vocab": vocab, "corpus_words": total}


def paragraph_vectors_words_per_sec(vocab: int = 5000, n_docs: int = 20000,
                                    doc_len: int = 20, epochs: int = 1,
                                    seq_algo: str = "dbow") -> Dict:
    """Labeled-sequence (doc2vec) throughput — the bulk-path analogue of
    ``word2vec_words_per_sec`` with one unique label per document
    (reference: PV rides the same native aggregates,
    ``SkipGram.java:271-283``)."""
    from ..nlp.paragraph_vectors import ParagraphVectors
    from ..nlp.sentence_iterator import LabelledDocument

    docs = [LabelledDocument(s, ["DOC_%d" % i]) for i, s in
            enumerate(_zipf_sentences(vocab, n_docs, doc_len))]
    total = n_docs * doc_len * epochs
    pv = ParagraphVectors(documents=docs, sequence_algorithm=seq_algo,
                          layer_size=128, window=5, negative=5,
                          epochs=epochs, seed=1, min_word_frequency=1)
    cold, steady = _cold_steady_fit(pv, total)
    return {"metric": f"paragraph_vectors_{seq_algo}_words_per_sec",
            "value": round(steady, 1), "unit": "words/sec",
            "cold_words_per_sec": round(cold, 1), "vocab": vocab,
            "n_docs": n_docs, "corpus_words": total}


def transformer_lm_step_time(batch: int = 16, seq: int = 512,
                             embed: int = 512, n_layers: int = 8,
                             n_heads: int = 8, vocab: int = 8192,
                             impls=("auto", "flash", "reference"),
                             nbatch: int = 5, epochs: int = 2,
                             blocks: int = 3) -> List[Dict]:
    """TransformerLM train throughput + achieved TFLOP/s per attention impl
    (VERDICT r2 item 6 / r3 item 1: the beyond-reference tier measured like
    the parity tier).  Flops use the causal PaLM-style estimate
    6·T·(12·L·E² + E·V) matmul + 6·L·B·S²·E attention (fwd+bwd).

    Sparse integer labels (the LM-natural target — one-hot reads an extra
    ~268 MB HBM/step at V=8192) and the device-resident epoch scan
    (``fit_on_device``, one dispatch per epoch) so the row is not bound by
    per-step host dispatch."""
    import jax.numpy as jnp

    from ..models import TransformerLM

    from ..observability.profiler import resolve_card_flops

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (batch * nbatch, seq + 1))
    x = jnp.asarray(ids[:, :-1])
    y = jnp.asarray(ids[:, 1:])
    tokens = batch * seq
    # analytic fallback only: when a committed graftaudit card exists for
    # the program, its COUNTED flops are authoritative (same source the
    # profiler's training_mfu uses) and the estimate below is unused
    analytic_flops = (
        6 * tokens * (12 * n_layers * embed * embed + embed * vocab)
        + 6 * n_layers * batch * seq * seq * embed)
    out = []
    for impl in impls:
        program = f"transformer_lm[{impl},s={seq}]"
        card_flops = resolve_card_flops(program)
        flops = card_flops if card_flops is not None else analytic_flops
        model = TransformerLM(vocab_size=vocab, seq_len=seq, embed=embed,
                              n_layers=n_layers, n_heads=n_heads,
                              attn_impl=impl, sparse_labels=True,
                              compute_dtype="bfloat16").init()
        ms = _scan_step_ms(model, x, y, batch, nbatch, epochs=epochs,
                           blocks=blocks)
        out.append({
            "metric": f"transformer_lm_step_ms[{impl},s={seq}]",
            "value": round(ms, 3), "unit": "ms/step",
            "batch": batch, "seq": seq, "embed": embed,
            "n_layers": n_layers, "sparse_labels": True,
            "tokens_per_sec": round(tokens / ms * 1e3, 1),
            "achieved_tflops": round(flops / ms / 1e9, 2),
            "flops_source": "card" if card_flops is not None else "analytic",
        })
    return out


def step_time_ms(seqs=(128, 512, 2048), dtypes=("float32", "bfloat16"),
                 batch: int = 16, big_mult: int = 4, embed: int = 256,
                 n_layers: int = 4, n_heads: int = 8, vocab: int = 2048,
                 steps: int = 20, adapt_cap: int = 2000,
                 compile_cost_s=None, step_cost_s=None) -> List[Dict]:
    """Per-step train time through the PER-STEP fit path under a
    mixed-size workload, auto shape policy vs off (ISSUE 6 acceptance:
    the s=128 bucketing regression must stay within 10% of the
    off-policy reference).

    Each row reproduces the regression scenario directly: one batch at
    ``batch x big_mult`` compiles a large bucket, then the workload
    settles on ``batch``-sized steps.  The pre-cost-model auto policy
    padded EVERY small step onto the big bucket (paying ``big_mult``x
    the flops forever); the ski-rental cost model pads only until the
    cumulative waste rivals one compile, then gives the recurring size
    its own executable — ``adapt_steps`` reports how many padded steps
    that took.  The timed window starts after adaptation, so ``value``
    is the steady per-step cost a long-running job pays.  The f32/bf16
    sweep makes the PrecisionPolicy step-time win visible on the same
    trajectory (``DL4J_TPU_BENCH_DTYPE``-independent: both always run).
    """
    import jax.numpy as jnp

    from ..data.shapes import ShapePolicy
    from ..models import TransformerLM

    rng = np.random.default_rng(0)
    out = []
    for seq in seqs:
        ids_big = rng.integers(0, vocab, (batch * big_mult, seq + 1))
        ids = rng.integers(0, vocab, (batch, seq + 1))
        xb, yb = jnp.asarray(ids_big[:, :-1]), jnp.asarray(ids_big[:, 1:])
        x, y = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])
        for dt in dtypes:
            per_policy = {}
            for mode in ("auto", "off"):
                model = TransformerLM(
                    vocab_size=vocab, seq_len=seq, embed=embed,
                    n_layers=n_layers, n_heads=n_heads, sparse_labels=True,
                    compute_dtype=None if dt == "float32" else dt).init()
                model.shape_policy = ShapePolicy(
                    mode, compile_cost_s=compile_cost_s,
                    step_cost_s=step_cost_s)
                model.fit_batch((xb, yb))   # the large compiled bucket
                # adaptation: drive small steps (through fit, so the
                # steady step-seconds histogram feeds the cost model)
                # until the policy stops padding onto the big bucket
                adapted = mode == "off"
                n_adapt = 0
                while not adapted and n_adapt < adapt_cap:
                    chunk = min(25, adapt_cap - n_adapt)
                    model.fit(iter([(x, y, None, None)] * chunk))
                    n_adapt += chunk
                    seen = {tuple(e[:2]): e[2] for e in
                            model.shape_policy.snapshot()["seen"]}
                    adapted = batch in (seen.get(("train", "batch")) or [])
                model.fit_batch((x, y))     # warm the steady executable
                t0 = monotonic_s()
                model.fit(iter([(x, y, None, None)] * steps))
                # _fit_one syncs the loss per step: the clock closes on
                # device completion, not enqueue
                ms = (monotonic_s() - t0) / steps * 1e3
                per_policy[mode] = (ms, n_adapt)
            auto_ms, n_adapt = per_policy["auto"]
            off_ms, _ = per_policy["off"]
            tag = "f32" if dt == "float32" else dt
            out.append({
                "metric": f"step_time_ms[s={seq},{tag}]",
                "value": round(auto_ms, 3), "unit": "ms/step (auto policy)",
                "off_policy_ms": round(off_ms, 3),
                "vs_off": round(auto_ms / off_ms, 3) if off_ms else None,
                "adapt_steps": n_adapt,
                "batch": batch, "seq": seq, "dtype": dt,
                "big_bucket": batch * big_mult,
                "tokens_per_sec": round(batch * seq / auto_ms * 1e3, 1),
            })
    return out


class _PipelineBenchSource:
    """Picklable source factory for the input-pipeline benchmark: every ETL
    worker regenerates the same synthetic image set (cheaper and more
    deterministic than shipping arrays through pickle) and batches it."""

    def __init__(self, n: int, image: int = 32, channels: int = 3,
                 batch: int = 64, n_classes: int = 10, seed: int = 0):
        self.n, self.image, self.channels = n, image, channels
        self.batch, self.n_classes, self.seed = batch, n_classes, seed

    def __call__(self):
        from ..data.dataset import INDArrayDataSetIterator
        rng = np.random.default_rng(self.seed)
        x = rng.standard_normal(
            (self.n, self.image, self.image, self.channels),
            dtype=np.float32)
        y = np.zeros((self.n, self.n_classes), np.float32)
        y[np.arange(self.n), rng.integers(0, self.n_classes, self.n)] = 1.0
        return INDArrayDataSetIterator(x, y, self.batch)


class _PipelineBenchTransform:
    """Deliberately CPU-heavy augmentation (CIFAR-style crop/flip/cutout
    plus repeated per-image standardization) so host ETL, not the tiny
    dense step, is the bound — the workload the overlapped pipeline exists
    for.  Module-level (picklable) so ETL worker processes can receive it;
    exposes both the ``ImageTransform.transform`` protocol (for
    ``TransformingDataSetIterator``) and plain ``__call__``."""

    def __init__(self, repeats: int = 40):
        from ..data.transforms import (ComposeTransform, CutoutTransform,
                                       RandomCropTransform,
                                       RandomFlipTransform)
        self.repeats = repeats
        self.aug = ComposeTransform([RandomCropTransform(4),
                                     RandomFlipTransform(),
                                     CutoutTransform(8)])

    def transform(self, feats, rng):
        out = self.aug.transform(feats, rng)
        for _ in range(self.repeats):
            # 5-point smoothing + per-image standardization: ~5 ms per
            # repeat at (64, 64, 64, 3) — repeats=40 puts batch ETL around
            # 200 ms, far above the tiny dense step, so the pipeline (not
            # the chip) is what this benchmark exercises
            out = (out + np.roll(out, 1, axis=1) + np.roll(out, -1, axis=1)
                   + np.roll(out, 1, axis=2)
                   + np.roll(out, -1, axis=2)) * 0.2
            mu = out.mean(axis=(1, 2, 3), keepdims=True)
            sd = out.std(axis=(1, 2, 3), keepdims=True) + 1e-6
            out = (out - mu) / sd
        return out.astype(np.float32)

    __call__ = transform


def input_pipeline_examples_per_sec(batch: int = 64, image: int = 64,
                                    channels: int = 3, nbatch: int = 120,
                                    workers: int = 0, depth: int = 3,
                                    runs: int = 2) -> Dict:
    """Input-bound training throughput: single-thread async prefetch
    (``AsyncDataSetIterator``, the pre-pipeline path) vs the overlapped
    pipeline (``MultiprocessETLIterator`` workers + ``DevicePrefetchIterator``
    H2D-ahead).  The model is a deliberately tiny dense net so ETL >= step;
    ``overlap_speedup`` is the headline ratio (ISSUE 3 acceptance: >= 1.5x
    on hardware with spare host cores — worker *spawn* time is inside the
    clock, as a real user would pay it each epoch).  ``workers=0`` picks
    ``min(4, cpu_count - 1)``."""
    import os as _os

    from ..data.dataset import AsyncDataSetIterator
    from ..data.pipeline import build_input_pipeline
    from ..data.transforms import TransformingDataSetIterator
    from ..nn.conf.input_type import InputType
    from ..nn.conf.multi_layer import NeuralNetConfiguration
    from ..nn.conf.updaters import Adam
    from ..nn.layers.feedforward import DenseLayer, OutputLayer
    from ..nn.multilayer import MultiLayerNetwork

    if workers <= 0:
        workers = max(1, min(4, (_os.cpu_count() or 2) - 1))
    n = batch * nbatch
    source = _PipelineBenchSource(n, image, channels, batch)
    tf = _PipelineBenchTransform()

    conf = (NeuralNetConfiguration.builder().seed(3)
            .updater(Adam(learning_rate=1e-3)).list()
            .layer(DenseLayer(n_out=64, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.convolutional(image, image, channels))
            .build())
    model = MultiLayerNetwork(conf).init()

    # compile warm-up + raw per-batch costs (ETL vs step) for the
    # input-boundedness sanity flag
    probe = next(iter(source()))
    feats = tf.transform(probe.features, np.random.default_rng(0))
    model.fit((feats, probe.labels))
    t0 = monotonic_s()
    model.fit((feats, probe.labels))
    step_ms = (monotonic_s() - t0) * 1e3
    t0 = monotonic_s()
    tf.transform(probe.features, np.random.default_rng(1))
    etl_ms = (monotonic_s() - t0) * 1e3

    def timed_fit(iterator) -> float:
        t0 = monotonic_s()
        model.fit(iterator)
        model.get_score()          # _fit_one already synced the final loss
        return n / (monotonic_s() - t0)

    async_rates, pipe_rates = [], []
    for _ in range(runs):
        async_rates.append(timed_fit(AsyncDataSetIterator(
            TransformingDataSetIterator(source(), tf, seed=1),
            queue_size=depth)))
        pipe_rates.append(timed_fit(build_input_pipeline(
            source, tf, num_workers=workers, depth=depth, seed=1)))
    async_rate = float(np.median(async_rates))
    pipe_rate = float(np.median(pipe_rates))
    return {"metric": "input_pipeline_examples_per_sec",
            "value": round(pipe_rate, 1), "unit": "examples/sec",
            "async_examples_per_sec": round(async_rate, 1),
            "overlap_speedup": round(pipe_rate / async_rate, 2),
            "batch": batch, "nbatch": nbatch, "workers": workers,
            "depth": depth, "host_cpus": _os.cpu_count(),
            "etl_ms_per_batch": round(etl_ms, 1),
            "step_ms_per_batch": round(step_ms, 1),
            "input_bound": bool(etl_ms > step_ms)}


def serving_latency(concurrency: int = 16,
                    n_requests: int = 400, model=None) -> List[Dict]:
    """Serving under load (VERDICT r3 item 8; mirror
    ``ParallelInference.java:32`` + ``InferenceMode.BATCHED``): p50/p99
    single-request latency and delivered throughput at a stated
    concurrency, batched (dynamic coalescing) vs unbatched (INPLACE
    synchronous).  Requests are singleton feature rows fired from
    ``concurrency`` client threads against one LeNet-sized model."""
    from ..models import LeNet
    from ..parallel.inference import InferenceMode, ParallelInference

    if model is None:
        model = LeNet().init()
    rng = np.random.default_rng(0)
    probe = rng.standard_normal((784,)).astype(np.float32)  # LeNet takes
    out = []                 # flat MNIST rows (feed-forward input + reshape)
    for mode in (InferenceMode.BATCHED, InferenceMode.INPLACE):
        pi = ParallelInference(model, inference_mode=mode,
                               max_batch_size=32)
        # warm every coalescing bucket so no compile lands in a timed
        # request (XLA compiles per padded shape)
        for b in (1, 2, 4, 8, 16, 32):
            pi.output(np.stack([probe] * b))
        lats, wall, _ = _closed_loop(
            lambda: np.asarray(pi.output(probe)),  # host-synced result
            concurrency, n_requests)
        pi.shutdown()
        lats_ms = np.asarray(lats) * 1e3
        out.append({
            "metric": f"serving_latency_ms[{mode.lower()},c={concurrency}]",
            "value": round(float(np.percentile(lats_ms, 50)), 2),
            "unit": "ms p50", "concurrency": concurrency,
            "p99_ms": round(float(np.percentile(lats_ms, 99)), 2),
            "requests": len(lats),
            "requests_per_sec": round(len(lats) / wall, 1),
        })
    return out


def _closed_loop(call, concurrency: int, n_requests: int):
    """Closed-loop load: ``concurrency`` client threads each issue
    ``n_requests // concurrency`` back-to-back requests.  Returns
    (sorted latencies in seconds, wall seconds, error count)."""
    import threading

    lats: List[float] = []
    errors = [0]
    lock = threading.Lock()
    per_worker = max(1, n_requests // concurrency)

    def client():
        mine = []
        errs = 0
        for _ in range(per_worker):
            t0 = monotonic_s()
            try:
                call()
            except Exception:
                errs += 1
                continue
            mine.append(monotonic_s() - t0)
        with lock:
            lats.extend(mine)
            errors[0] += errs

    threads = [threading.Thread(target=client)
               for _ in range(concurrency)]
    t0 = monotonic_s()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = monotonic_s() - t0
    return sorted(lats), wall, errors[0]


def serve_latency_ms(concurrencies=(1, 16, 64), n_requests: int = 384,
                     model=None, max_batch: int = 32,
                     queue_limit: int = 1024) -> List[Dict]:
    """Serving-engine bench (ISSUE 8): p50/p99 single-request latency and
    delivered req/s from closed-loop clients, the continuous-batching
    :class:`serving.ServingEngine` vs the per-request baseline (one
    synchronous forward per request — the pre-engine serving path), at
    each stated concurrency.  Engine rows carry ``vs_per_request``
    (req/s ratio — the acceptance gate at c=16) and
    ``steady_recompiles`` (XLA traces after warmup, which the warmed
    bucket ladder must keep at 0)."""
    from ..models import LeNet
    from ..parallel.inference import InferenceMode, ParallelInference
    from ..serving.engine import ServingEngine

    if model is None:
        model = LeNet().init()
    try:
        feat = tuple(model.conf.input_type.shape(-1)[1:])
    except Exception:
        feat = (784,)
    probe = np.random.default_rng(0).standard_normal(feat).astype(np.float32)

    def rows_for(impl: str, call, concurrency: int, extra: Dict) -> Dict:
        lats, wall, errs = _closed_loop(call, concurrency, n_requests)
        lats_ms = np.asarray(lats) * 1e3
        return {
            "metric": f"serve_latency_ms[{impl},c={concurrency}]",
            "value": round(float(np.percentile(lats_ms, 50)), 2),
            "unit": "ms p50", "impl": impl, "concurrency": concurrency,
            "p99_ms": round(float(np.percentile(lats_ms, 99)), 2),
            "requests": len(lats), "errors": errs,
            "requests_per_sec": round(len(lats) / wall, 1),
            **extra,
        }

    out: List[Dict] = []
    baseline_rps: Dict[int, float] = {}
    # per-request baseline: every request pays its own synchronous forward
    pi = ParallelInference(model, InferenceMode.INPLACE)
    pi.output(probe)                       # compile the singleton shape
    for c in concurrencies:
        row = rows_for("per_request", lambda: pi.output(probe), c, {})
        baseline_rps[c] = row["requests_per_sec"]
        out.append(row)
    pi.shutdown()

    engine = ServingEngine(model, max_batch_size=max_batch,
                           queue_limit=queue_limit)
    try:
        engine.warmup()                    # compile the bucket ladder
        for c in concurrencies:
            row = rows_for("engine", lambda: engine.predict(probe), c, {})
            if baseline_rps.get(c):
                row["vs_per_request"] = round(
                    row["requests_per_sec"] / baseline_rps[c], 2)
            # read AFTER the loop: these count the timed window's work
            row["steady_recompiles"] = engine.steady_recompiles
            row["batches_dispatched"] = engine.batches_dispatched
            out.append(row)
    finally:
        engine.shutdown()
    return out


def decode_tokens_per_sec(model=None, max_slots: int = 8,
                          max_seq: int = 128,
                          mixes=(("decode_heavy", 12, 8, 48),
                                 ("prefill_heavy", 12, 96, 8)),
                          ) -> List[Dict]:
    """Generation-engine bench (ISSUE 11): delivered tokens/sec from the
    slot-batched continuous-batching :class:`generation.GenerationEngine`
    vs the naive pre-subsystem baseline — one FULL re-forward per token,
    one request at a time — on a prefill-heavy mix (long prompts, short
    continuations: the prefill ladder dominates) and a decode-heavy mix
    (short prompts, long continuations: the fixed-shape decode step
    dominates).  Engine rows carry ``vs_naive`` (the acceptance gate:
    batching `max_slots` sequences through ONE decode program per step
    must beat re-running the stack per token) and ``steady_recompiles``,
    which the warmed two-program set must keep at 0.

    The naive baseline runs at a FIXED padded shape (history padded to
    ``max_seq``) so it pays one compile, not one per history length —
    the comparison is engine-vs-dispatch-pattern, not engine-vs-
    recompile-storm.  Greedy sampling on both sides keeps the token
    streams comparable (the bench asserts throughput, the test suite
    asserts the streams match)."""
    from ..generation import GenerationConfig, GenerationEngine
    from ..models import TransformerLM

    if model is None:
        model = TransformerLM(vocab_size=64, seq_len=max_seq, embed=64,
                              n_layers=2, n_heads=4).init()
    rng = np.random.default_rng(0)
    vocab = model.conf.layers[-1].n_out

    def naive_tokens(prompt, n) -> list:
        """Per-token full re-forward at one padded shape."""
        hist = list(prompt)
        out = []
        for _ in range(n):
            padded = np.zeros((1, max_seq), np.int32)
            padded[0, :len(hist)] = hist
            probs = np.asarray(model.output(padded))
            tok = int(probs[0, len(hist) - 1].argmax())
            out.append(tok)
            hist.append(tok)
        return out

    rows: List[Dict] = []
    engine = GenerationEngine.for_model(
        model, GenerationConfig(max_slots=max_slots, max_seq=max_seq,
                                queue_limit=4096))
    try:
        engine.warmup()
        cache_bytes = engine.ring.cache_bytes
        slots_per_gb = round(max_slots / (cache_bytes / 2**30), 1)
        naive_tokens([1], 1)                 # compile the naive shape too
        for mix, n_requests, prompt_len, new_tokens in mixes:
            prompts = [rng.integers(0, vocab, prompt_len).tolist()
                       for _ in range(n_requests)]
            t0 = monotonic_s()
            total_naive = sum(len(naive_tokens(p, new_tokens))
                              for p in prompts)
            naive_wall = monotonic_s() - t0
            t0 = monotonic_s()
            reqs = [engine.submit(p, max_new_tokens=new_tokens)
                    for p in prompts]
            results = [r.future.result(timeout=600) for r in reqs]
            engine_wall = monotonic_s() - t0
            total = sum(len(r.tokens) for r in results)
            tps = total / engine_wall
            naive_tps = total_naive / naive_wall
            rows.append({
                "metric": f"decode_tokens_per_sec[{mix}]",
                "value": round(tps, 1),
                "unit": "tokens/sec", "mix": mix,
                "requests": n_requests, "prompt_len": prompt_len,
                "new_tokens": new_tokens, "max_slots": max_slots,
                "tokens": total,
                "naive_tokens_per_sec": round(naive_tps, 1),
                "vs_naive": round(tps / naive_tps, 2) if naive_tps else None,
                "steady_recompiles": engine.steady_recompiles,
                "decode_steps": engine.decode_steps,
                "cache_bytes": cache_bytes,
                "slots_per_gb": slots_per_gb,
            })
    finally:
        engine.shutdown()
    rows.append(_slot_capacity_row(model, max_slots, max_seq))
    return rows


def _dense_cache_bytes(model, max_slots: int, max_seq: int) -> int:
    """Byte cost of the REMOVED dense slot ring at this geometry — the
    baseline the capacity row is measured against, computed analytically
    (``jax.eval_shape`` of exactly the per-layer carries the ring used
    to allocate: K/V ``[max_slots, heads, max_seq, head_dim]`` plus
    validity/position rows), so the comparison survives the ring's
    deletion without a dense engine to measure."""
    import jax
    import jax.numpy as jnp

    from ..generation.programs import _fresh_carry, carried_layers

    total = 0
    for lc in carried_layers(model.conf).values():
        shapes = jax.eval_shape(
            lambda lc=lc: _fresh_carry(lc, max_slots, max_seq))
        if isinstance(shapes, dict) and "pos" in shapes and \
                getattr(shapes["pos"], "ndim", 0) == 0:
            # the ring vectorized scalar stream positions per slot
            shapes = dict(shapes, pos=jax.ShapeDtypeStruct(
                (max_slots,), jnp.int32))
        total += sum(int(np.prod(s.shape)) * s.dtype.itemsize
                     for s in jax.tree_util.tree_leaves(shapes))
    return total


def _slot_capacity_row(model, max_slots: int, max_seq: int) -> Dict:
    """The paged-KV memory claim as a pinned number: at the dense ring's
    cache-byte budget (computed analytically — the ring itself is gone),
    how many slots can decode CONCURRENTLY on a short-actual-length
    workload (each sequence fits ONE block — at the bench default,
    prompt 8 + 8 generated = 16 tokens vs a dense slot priced at
    ``max_seq=128``)?  The paged pool is sized to the dense-equivalent
    block count (trash block included), the paged engine to 4x the
    slots, and the row verifies the whole fleet was simultaneously
    resident (``peak_active``) with zero steady recompiles."""
    from ..generation import GenerationConfig, GenerationEngine

    rng = np.random.default_rng(7)
    vocab = model.conf.layers[-1].n_out
    # one block per sequence, 8 blocks per dense-slot-equivalent: the
    # short-actual-length geometry scales with max_seq so toy configs
    # exercise the same row contract the real bench scale pins
    block = max(2, max_seq // 8)
    dense_bytes = _dense_cache_bytes(model, max_slots, max_seq)
    paged_slots = 4 * max_slots
    # the dense ring's K/V byte budget expressed in blocks (trash block
    # INCLUDED — the pool must not exceed the dense bytes it replaces)
    n_blocks = max_slots * (max_seq // block)
    paged = GenerationEngine.for_model(
        model, GenerationConfig(max_slots=paged_slots, max_seq=max_seq,
                                block_size=block,
                                n_blocks=n_blocks, queue_limit=4096))
    try:
        paged.warmup()
        paged_bytes = paged.ring.cache_bytes
        # queue the whole fleet before a tick can admit any of it: ticks
        # serialize on the engine step lock, so holding it across the
        # submits makes admission one batch and the simultaneous-
        # residency claim deterministic (short requests would otherwise
        # finish before the submit loop does)
        with paged._step_lock:
            reqs = [paged.submit(
                        rng.integers(0, vocab, block // 2).tolist(),
                        max_new_tokens=block - block // 2)
                    for _ in range(paged_slots)]
        results = [r.future.result(timeout=600) for r in reqs]
        assert all(r.finish == "length" for r in results)
        peak = paged.ring.peak_active
        return {
            "metric": "decode_tokens_per_sec[slot_capacity]",
            "value": round(paged_slots / max_slots, 2),
            "unit": "x_dense_slots",
            "dense_slots": max_slots, "paged_slots": paged_slots,
            "peak_active": peak, "block_size": block,
            "n_blocks": n_blocks, "max_seq": max_seq,
            "cache_bytes": paged_bytes, "dense_cache_bytes": dense_bytes,
            "bytes_vs_dense": round(paged_bytes / dense_bytes, 3),
            "slots_per_gb": round(paged_slots / (paged_bytes / 2**30), 1),
            "dense_slots_per_gb": round(
                max_slots / (dense_bytes / 2**30), 1),
            "steady_recompiles": paged.steady_recompiles,
        }
    finally:
        paged.shutdown()


def ttft_ms(model=None, max_slots: int = 4, max_seq: int = 128,
            n_requests: int = 16, prefix_len: int = 64,
            suffix_len: int = 8, new_tokens: int = 4) -> List[Dict]:
    """Time-to-first-token under a shared-prefix-heavy admission mix
    (ISSUE 19): every request carries the same ``prefix_len``-token
    system/few-shot header plus a unique ``suffix_len`` tail — the
    workload prefix sharing exists for.  Two arms, identical requests:

    - ``paged_cold``: paged cache, sharing disabled — every admission
      prefills its full prompt;
    - ``paged_shared``: paged cache with the content-hash prefix
      registry — after the first request registers the header blocks,
      every later admission adopts them and prefills only its suffix.

    Requests run SEQUENTIALLY (TTFT here isolates the prefill path, not
    queueing).  Rows carry p50/p99 TTFT, prefill tokens saved, the
    shared-vs-cold ratio on the shared arm (the >= 2x acceptance gate),
    and the steady-recompile counter (the suffix ladder must absorb
    every suffix shape at warmup)."""
    from ..generation import GenerationConfig, GenerationEngine
    from ..models import TransformerLM

    if model is None:
        model = TransformerLM(vocab_size=64, seq_len=max_seq, embed=64,
                              n_layers=2, n_heads=4).init()
    rng = np.random.default_rng(3)
    vocab = model.conf.layers[-1].n_out
    prefix = rng.integers(0, vocab, prefix_len).tolist()
    prompts = [prefix + rng.integers(0, vocab, suffix_len).tolist()
               for _ in range(n_requests)]

    arms = (("paged_cold", dict(prefix_sharing=False)),
            ("paged_shared", dict(prefix_sharing=True)))
    rows: List[Dict] = []
    cold_p50 = None
    for arm, cfg_kw in arms:
        engine = GenerationEngine.for_model(
            model, GenerationConfig(max_slots=max_slots, max_seq=max_seq,
                                    **cfg_kw))
        try:
            engine.warmup()
            ttfts = []
            for p in prompts:
                req = engine.submit(p, max_new_tokens=new_tokens)
                req.future.result(timeout=600)
                ttfts.append((req.t_first - req.t_submit) * 1e3)
            stats = engine.status().get("kv") or {}
            p50 = float(np.percentile(ttfts, 50))
            if arm == "paged_cold":
                cold_p50 = p50
            row = {
                "metric": f"ttft_ms[{arm}]",
                "value": round(p50, 3), "unit": "ms", "arm": arm,
                "p50_ms": round(p50, 3),
                "p99_ms": round(float(np.percentile(ttfts, 99)), 3),
                "requests": n_requests, "prefix_len": prefix_len,
                "suffix_len": suffix_len, "new_tokens": new_tokens,
                "prefill_tokens_saved": stats.get("prefix_tokens_saved",
                                                  0),
                "prefix_hits": stats.get("prefix_hits", 0),
                "steady_recompiles": engine.steady_recompiles,
            }
            if arm == "paged_shared" and cold_p50:
                row["vs_cold"] = round(cold_p50 / p50, 2)
            rows.append(row)
        finally:
            engine.shutdown()
    return rows


def compile_reuse(hidden: int = 64, features: int = 16, classes: int = 5,
                  batch: int = 32) -> Dict:
    """Compilation-reuse benchmark (ISSUE 4): cold first-step compile vs a
    ``clone()``'s first step through the shared trace cache, plus the
    compile count of a ragged-last-batch ``fit`` under shape bucketing.

    The headline ``value`` is the clone-reuse speedup (cold first-step
    wall time / clone first-step wall time): >> 1 means replica K's
    time-to-first-step is dispatch, not an XLA compile.  ``_fit_one``
    host-syncs the loss, so both step timings close on device completion.
    """
    import jax.numpy as jnp

    from .. import (InputType, MultiLayerNetwork, NeuralNetConfiguration)
    from ..nn.conf.updaters import Adam
    from ..nn.layers.feedforward import DenseLayer, OutputLayer
    from ..observability.registry import default_registry

    def build():
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Adam(learning_rate=0.01)).list()
                .layer(DenseLayer(n_out=hidden, activation="relu"))
                .layer(OutputLayer(n_out=classes, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(features))
                .build())
        return MultiLayerNetwork(conf).init()

    reg = default_registry()

    def train_step_compiles() -> float:
        c = reg.get("training_compile_total")
        return 0.0 if c is None else c.labels("train_step").value

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, features),
                                        dtype=np.float32))
    y = jnp.asarray(np.eye(classes, dtype=np.float32)[
        rng.integers(0, classes, batch)])

    model = build()
    t0 = monotonic_s()
    model.fit_batch((x, y))                     # cold: trace + compile
    cold_s = monotonic_s() - t0

    replica = model.clone()
    before = train_step_compiles()
    t0 = monotonic_s()
    replica.fit_batch((x, y))                   # shared-cache reuse
    clone_s = monotonic_s() - t0
    clone_compiles = train_step_compiles() - before

    # ragged last batch: the tail pads onto the steady bucket, so the
    # whole fit costs at most one extra (label-masked) compile
    tail = max(1, batch // 3)
    before = train_step_compiles()
    model.fit(iter([(x, y, None, None),
                    (x[:tail], y[:tail], None, None)]))
    ragged_compiles = train_step_compiles() - before

    speedup = cold_s / max(clone_s, 1e-9)
    return {"metric": "compile_reuse", "value": round(speedup, 1),
            "unit": "x cold/clone first-step",
            "cold_first_step_ms": round(cold_s * 1e3, 1),
            "clone_first_step_ms": round(clone_s * 1e3, 1),
            "clone_extra_compiles": clone_compiles,
            "ragged_fit_compiles": ragged_compiles}


def checkpoint_overhead(hidden: int = 128, features: int = 64,
                        classes: int = 10, batch: int = 64,
                        steps: int = 16, save_every: int = 4) -> Dict:
    """Checkpointing-overhead benchmark (ISSUE 5): training stall per
    checkpoint from a sync (blocking) save vs an async (background,
    double-buffered) save, plus the committed-bytes write rate.

    ``value`` is the ASYNC stall in ms/save — what production training
    actually pays per checkpoint: the host snapshot only, with the write
    overlapped on the manager's worker thread across the following
    ``save_every - 1`` uncheckpointed steps (saving EVERY step would
    drain the double buffer at disk speed — real cadences leave the
    writer headroom).  ``sync_stall_ms`` is the full in-line write cost
    the async path hides.  Baseline and checkpointed loops run the same
    compiled step (warm-up excluded); ``_fit_one`` host-syncs the loss,
    so timings close on device completion.
    """
    import shutil
    import tempfile

    import jax.numpy as jnp

    from .. import (InputType, MultiLayerNetwork, NeuralNetConfiguration)
    from ..faulttolerance.checkpoint import CheckpointManager
    from ..nn.conf.updaters import Adam
    from ..nn.layers.feedforward import DenseLayer, OutputLayer

    def build():
        conf = (NeuralNetConfiguration.builder().seed(11)
                .updater(Adam(learning_rate=0.01)).list()
                .layer(DenseLayer(n_out=hidden, activation="relu"))
                .layer(DenseLayer(n_out=hidden, activation="relu"))
                .layer(OutputLayer(n_out=classes, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(features))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((batch, features),
                                        dtype=np.float32))
    y = jnp.asarray(np.eye(classes, dtype=np.float32)[
        rng.integers(0, classes, batch)])
    model = build()
    model.fit_batch((x, y))                     # compile + warm

    n_saves = max(1, steps // save_every)

    def loop_s(save=None):
        t0 = monotonic_s()
        for i in range(steps):
            model.fit_batch((x, y))
            if save is not None and (i + 1) % save_every == 0:
                save()
        return monotonic_s() - t0

    base_s = loop_s()
    workdir = tempfile.mkdtemp(prefix="dl4j_ckpt_bench_")
    try:
        sync_mgr = CheckpointManager(os.path.join(workdir, "sync"),
                                     keep_last=2, background=False)
        sync_s = loop_s(lambda: sync_mgr.save(model))
        ckpt_path = sync_mgr.latest()
        nbytes = sum(
            os.path.getsize(os.path.join(ckpt_path, f))
            for f in os.listdir(ckpt_path)) if ckpt_path else 0
        async_mgr = CheckpointManager(os.path.join(workdir, "async"),
                                      keep_last=2, background=True)
        async_s = loop_s(lambda: async_mgr.save(model))
        async_mgr.wait()
        # steady-state async stall: save() with the writer idle (the
        # production regime — checkpoint cadence >> write time) pays only
        # the host snapshot + thread handoff.  The loop numbers above
        # additionally capture double-buffer drain when this toy step
        # outruns the disk.
        t0 = monotonic_s()
        async_mgr.save(model)
        idle_stall_s = monotonic_s() - t0
        async_mgr.wait()
        # isolate the write itself for the bytes/sec figure
        t0 = monotonic_s()
        sync_mgr.save(model, blocking=True)
        write_s = monotonic_s() - t0
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    sync_stall = (sync_s - base_s) / n_saves * 1e3
    async_stall = (async_s - base_s) / n_saves * 1e3
    return {"metric": "checkpoint_overhead",
            "value": round(idle_stall_s * 1e3, 3),
            "unit": "ms/save async stall (idle writer)",
            "sync_stall_ms": round(sync_stall, 3),
            "async_loop_stall_ms": round(async_stall, 3),
            "base_step_ms": round(base_s / steps * 1e3, 3),
            "save_every": save_every,
            "checkpoint_bytes": int(nbytes),
            "write_mb_per_sec": round(nbytes / max(write_s, 1e-9) / 1e6, 1)}


def recovery_time_ms(hidden: int = 24, features: int = 8, classes: int = 3,
                     n_batches: int = 12, batch: int = 16) -> Dict:
    """Recovery-time benchmark (ISSUE 7): wall time from an injected
    worker kill to the FIRST post-recovery training step, on both
    recovery paths of the parameter-averaging master:

    - **sync retry** — a transient failure: the master restores the
      round-start snapshot, sleeps the seeded backoff, and re-executes
      the same worker's chunk.  Recovery = backoff + snapshot restore.
    - **elastic degradation** — a permanent loss: the retry budget
      exhausts and the survivors re-chunk the dead worker's round NOW.
      Recovery = loss verdict (the last failed attempt) to the first
      replayed batch on a survivor.

    ``value`` is the sync-retry figure (the common transient case); the
    elastic figure rides along.  Timestamps come from the
    ``FaultInjector``'s per-worker fault/recovery bookkeeping, so the
    measurement is the master's actual reaction time, not a loop-level
    subtraction.
    """
    from ..faulttolerance.faults import FaultInjector
    from ..nn.conf.input_type import InputType
    from ..nn.conf.multi_layer import NeuralNetConfiguration
    from ..nn.conf.updaters import Adam
    from ..nn.layers.feedforward import DenseLayer, OutputLayer
    from ..nn.multilayer import MultiLayerNetwork
    from ..parallel.master import ParameterAveragingTrainingMaster

    def build():
        conf = (NeuralNetConfiguration.builder().seed(11)
                .updater(Adam(learning_rate=0.02)).list()
                .layer(DenseLayer(n_out=hidden, activation="tanh"))
                .layer(OutputLayer(n_out=classes, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(features))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(5)
    batches = []
    for _ in range(n_batches):
        x = rng.standard_normal((batch, features)).astype(np.float32)
        y = np.eye(classes, dtype=np.float32)[
            rng.integers(0, classes, batch)]
        batches.append((x, y))
    build().fit_batch(batches[0])               # compile + warm the cache

    def run(injector, max_retries):
        master = ParameterAveragingTrainingMaster(
            2, averaging_frequency=2, max_retries=max_retries,
            retry_backoff_s=0.02, fault_injector=injector)
        master.fit(build(), iter(batches))
        return injector.recoveries_s

    retry_rec = run(FaultInjector(seed=0).fail(worker=1, rnd=1, times=1),
                    max_retries=2)
    elastic_rec = run(FaultInjector(seed=0).fail(worker=1, rnd=1, times=-1),
                      max_retries=1)
    retry_ms = retry_rec[0] * 1e3 if retry_rec else None
    elastic_ms = elastic_rec[0] * 1e3 if elastic_rec else None
    return {"metric": "recovery_time_ms",
            "value": None if retry_ms is None else round(retry_ms, 2),
            "unit": "ms kill -> first post-recovery step (sync retry)",
            "elastic_ms": None if elastic_ms is None
            else round(elastic_ms, 2),
            "workers": 2, "retry_backoff_s": 0.02}


def lint_time_ms(paths=None, runs: int = 2) -> Dict:
    """graftlint wall-time benchmark (ISSUE 9): one full-package run
    through the public ``lint_paths`` API — 24 module rules off the
    shared per-file parse plus the whole-program concurrency pass
    (JX018–JX021).  The linter gates tier-1 and the developer loop, so a
    rule addition that blows up its wall time is a latency regression
    exactly like a slow train step; this row makes it round-over-round
    visible.  ``value`` is the MEDIAN of ``runs`` runs (process-cache
    effects make the first run the slowest)."""
    import sys
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # import under a TEMPORARY path entry: leaving the repo root on
    # sys.path would let its top-level packages (tools, tests, bench)
    # shadow a host application's same-named modules forever after
    added = repo_root not in sys.path
    if added:
        sys.path.insert(0, repo_root)
    try:
        from tools.graftlint import PROGRAM_RULES, RULES, \
            iter_python_files, lint_paths
    finally:
        if added:
            sys.path.remove(repo_root)
    if paths is None:
        paths = [os.path.join(repo_root, "deeplearning4j_tpu")]
    n_files = len(list(iter_python_files(paths)))
    times = []
    findings = []
    for _ in range(max(1, runs)):
        t0 = monotonic_s()
        findings = lint_paths(paths)
        times.append((monotonic_s() - t0) * 1e3)
    return {
        "metric": "lint_time_ms",
        "value": round(float(np.median(times)), 1),
        "unit": "ms full-package graftlint",
        "files": n_files,
        "rules": len(RULES) + len(PROGRAM_RULES),
        "findings": len(findings),
        "runs": len(times),
        "spread_ms": round(max(times) - min(times), 1),
    }


def audit_time_ms(include=None) -> Dict:
    """graftaudit wall-time benchmark (ISSUE 14; diff slice ISSUE 16):
    build the canonical program set through its production entry
    points, then run the full IR audit — jaxpr phase plus the
    partitioned-HLO compiles of every program — then the differential
    gate's budgets.json ceiling checks.  The audit gates tier-1
    (tests/test_audit.py, test_audit_diff.py) exactly like
    graftlint does, so rule/program additions that blow up its wall
    time are a CI-latency regression this row keeps round-over-round
    visible; the acceptance budget is the full run (build + audit)
    under 60s on the CPU rig.  One run — the dominant cost is XLA
    compiles, which the persistent jit caches would make a second run
    under-report.  Coverage is EXPLICIT: canonical programs the host
    couldn't build (a sharded dp on a single-device backend) land in
    ``skipped`` — a row claiming the full set while silently covering
    6 of 8 programs would hide exactly the layout regressions the
    audit exists to catch."""
    import sys
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # temporary path entry, same hygiene as lint_time_ms
    added = repo_root not in sys.path
    if added:
        sys.path.insert(0, repo_root)
    try:
        from tools.graftaudit import AUDIT_RULES, audit_programs
        from tools.graftaudit.canonical import (BUDGETS_PATH,
                                                CANONICAL_CONFIG,
                                                build_canonical)
        from tools.graftaudit.diff import check_budgets, load_budgets
    finally:
        if added:
            sys.path.remove(repo_root)
    t0 = monotonic_s()
    cs = build_canonical(include=include)
    build_ms = (monotonic_s() - t0) * 1e3
    t1 = monotonic_s()
    result = audit_programs(cs.programs, cs.suppressions,
                            CANONICAL_CONFIG)
    audit_ms = (monotonic_s() - t1) * 1e3
    # the differential-gate slice (ISSUE 16): the budgets.json ceiling
    # checks --diff-cards adds on top of the audit (AX010 card drift is
    # already inside audit_ms — CANONICAL_CONFIG arms it)
    t2 = monotonic_s()
    budgets = load_budgets(BUDGETS_PATH)
    # an include subset leaves non-matching budgeted programs
    # un-audited, not stale (same rule as the CLI's --programs)
    skipped_for_diff = dict(cs.skipped)
    if include is not None:
        audited = {ir_prog.name for ir_prog in result.irs}
        for name in budgets.get("programs", {}):
            if name not in audited and \
                    not any(s in name for s in include):
                skipped_for_diff.setdefault(name, "include subset")
    diff_findings, stale = check_budgets(
        result.irs, budgets, skipped_for_diff)
    diff_ms = (monotonic_s() - t2) * 1e3
    return {
        "metric": "audit_time_ms",
        "value": round(build_ms + audit_ms + diff_ms, 1),
        "unit": "ms full canonical-set IR audit (build + audit + diff)",
        "build_ms": round(build_ms, 1),
        "audit_ms": round(audit_ms, 1),
        "diff_ms": round(diff_ms, 1),
        "programs": len(result.irs),
        "skipped": sorted(cs.skipped),
        "rules": len(AUDIT_RULES),
        "findings": len(result.findings) + len(diff_findings),
        "stale_budgets": sorted(stale),
        "suppressed": sum(result.suppressed.values()),
        "budget_ms": 60000.0,
    }


def obs_overhead_ms(hidden: int = 256, features: int = 128,
                    classes: int = 10, batch: int = 128,
                    n_batches: int = 10,
                    runs: int = 21) -> Dict:
    """Observability-overhead benchmark (ISSUE 10): steady-state per-step
    train time with the runtime-forensics layer (flight recorder + health
    monitor) installed vs absent.  The fit loop's forensics feed
    (``_StepForensics``) captures one raw tuple per step and drains the
    buffer through the recorder ring and the monitor's EWMA detectors in
    warm batches — ~10us/step flat — so the target is <2%; this row
    keeps that claim measured instead of asserted, round over round.
    The workload is sized so the step does real compute (~3 ms on the
    1-core CPU test host, MLP 128->256->256->10 at batch 128): a
    dispatch-dominated sub-ms toy step would bill the flat microsecond
    cost against a denominator no real training run has.
    Shared-host noise between whole fits dwarfs the ~10us/step effect,
    so the design is PAIRED over SHORT fits: each round runs both arms
    back to back (order alternating to cancel cache-warmth bias) and
    the overhead is the median of the per-round deltas.  Chunks are kept
    to tens of milliseconds so both arms of a pair land inside one host
    drift window (~100 ms scheduler/freq timescale on the test host) —
    longer fits let drift straddle a pair and leak into the deltas;
    independent medians would report the drift, not the overhead.  Run
    it in a process of its own for the cleanest reading: heap left by an
    earlier benchmark in the same process inflates the cache-cold Python
    deltas."""
    from ..nn.conf.input_type import InputType
    from ..nn.conf.multi_layer import NeuralNetConfiguration
    from ..nn.conf.updaters import Adam
    from ..nn.layers.feedforward import DenseLayer, OutputLayer
    from ..nn.multilayer import MultiLayerNetwork
    from ..observability.health import HealthMonitor, set_health_monitor
    from ..observability.recorder import FlightRecorder, set_flight_recorder

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Adam(learning_rate=0.01)).list()
            .layer(DenseLayer(n_out=hidden, activation="tanh"))
            .layer(DenseLayer(n_out=hidden, activation="tanh"))
            .layer(OutputLayer(n_out=classes, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(features)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(13)
    batches = [(rng.standard_normal((batch, features)).astype(np.float32),
                np.eye(classes, dtype=np.float32)[
                    rng.integers(0, classes, batch)])
               for _ in range(n_batches)]
    net.fit(iter(batches[:2]), epochs=1)          # compile + warm

    def timed(enabled: bool) -> float:
        prev_rec = set_flight_recorder(
            FlightRecorder(capacity=256) if enabled else None)
        prev_mon = set_health_monitor(HealthMonitor() if enabled else None)
        try:
            t0 = monotonic_s()
            net.fit(iter(batches), epochs=1)
            return (monotonic_s() - t0) / n_batches * 1e3
        finally:
            set_flight_recorder(prev_rec)
            set_health_monitor(prev_mon)

    off_t, on_t, deltas = [], [], []
    for i in range(max(1, runs)):
        # alternate arm order: the second fit of a pair runs cache-warmer,
        # so a fixed order would systematically bias the deltas
        if i % 2 == 0:
            off = timed(False)
            on = timed(True)
        else:
            on = timed(True)
            off = timed(False)
        off_t.append(off)
        on_t.append(on)
        deltas.append(on - off)
    off_ms = float(np.median(off_t))
    on_ms = float(np.median(on_t))
    overhead_ms = float(np.median(deltas))
    overhead_pct = overhead_ms / off_ms * 100.0 if off_ms > 0 else None
    return {
        "metric": "obs_overhead_ms",
        "value": round(on_ms, 3),
        "unit": "ms/step recorder+monitor enabled",
        "off_ms": round(off_ms, 3),
        "overhead_ms": round(overhead_ms, 3),
        "overhead_pct": None if overhead_pct is None
        else round(overhead_pct, 2),
        "target_pct": 2.0,
        "steps": n_batches,
        "runs": max(1, runs),
    }


def profiler_overhead_ms(hidden: int = 256, features: int = 128,
                         classes: int = 10, batch: int = 128,
                         n_batches: int = 10,
                         runs: int = 21) -> Dict:
    """Step-profiler overhead benchmark (ISSUE 17 acceptance): steady
    per-step train time with the :class:`StepProfiler` armed (default-on
    config — sampled fence every 16 steps) vs ``DL4J_TPU_STEPPROF=0``.
    The per-step cost is a handful of ``perf_counter`` reads plus one
    buffered tuple append; the sampled fence amortizes its sync across
    the window — the target is <2%, measured here round over round.

    Same paired-short-fit design as :func:`obs_overhead_ms` (which see
    for the sizing/pairing rationale): both arms run back to back per
    round with alternating order, and overhead is the median of per-round
    deltas.

    The row also carries the attribution honesty check: one extra fit at
    ``sample_every=1`` (every step fenced) whose ``phase_share``
    breakdown and ``phase_coverage`` (phase sum over step wall on
    sampled steps, from :func:`~..observability.profiler.phase_summary`)
    must cover the wall within 5%."""
    from ..nn.conf.input_type import InputType
    from ..nn.conf.multi_layer import NeuralNetConfiguration
    from ..nn.conf.updaters import Adam
    from ..nn.layers.feedforward import DenseLayer, OutputLayer
    from ..nn.multilayer import MultiLayerNetwork
    from ..observability.profiler import CHANNEL, phase_summary
    from ..observability.recorder import FlightRecorder, set_flight_recorder

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Adam(learning_rate=0.01)).list()
            .layer(DenseLayer(n_out=hidden, activation="tanh"))
            .layer(DenseLayer(n_out=hidden, activation="tanh"))
            .layer(OutputLayer(n_out=classes, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(features)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(13)
    batches = [(rng.standard_normal((batch, features)).astype(np.float32),
                np.eye(classes, dtype=np.float32)[
                    rng.integers(0, classes, batch)])
               for _ in range(n_batches)]
    net.fit(iter(batches[:2]), epochs=1)          # compile + warm

    def timed(enabled: bool) -> float:
        # both arms keep the recorder installed so the delta isolates the
        # profiler itself, not the ring the records land in
        prev_rec = set_flight_recorder(FlightRecorder(capacity=256))
        prev_env = os.environ.get("DL4J_TPU_STEPPROF")
        os.environ["DL4J_TPU_STEPPROF"] = "1" if enabled else "0"
        try:
            t0 = monotonic_s()
            net.fit(iter(batches), epochs=1)
            return (monotonic_s() - t0) / n_batches * 1e3
        finally:
            set_flight_recorder(prev_rec)
            if prev_env is None:
                os.environ.pop("DL4J_TPU_STEPPROF", None)
            else:
                os.environ["DL4J_TPU_STEPPROF"] = prev_env

    off_t, on_t, deltas = [], [], []
    for i in range(max(1, runs)):
        if i % 2 == 0:
            off = timed(False)
            on = timed(True)
        else:
            on = timed(True)
            off = timed(False)
        off_t.append(off)
        on_t.append(on)
        deltas.append(on - off)
    off_ms = float(np.median(off_t))
    on_ms = float(np.median(on_t))
    overhead_ms = float(np.median(deltas))
    overhead_pct = overhead_ms / off_ms * 100.0 if off_ms > 0 else None

    # attribution honesty: one fully-fenced fit, phase sums vs step wall
    rec = FlightRecorder(capacity=256)
    prev_rec = set_flight_recorder(rec)
    prev_env = {k: os.environ.get(k)
                for k in ("DL4J_TPU_STEPPROF", "DL4J_TPU_STEPPROF_SAMPLE")}
    os.environ["DL4J_TPU_STEPPROF"] = "1"
    os.environ["DL4J_TPU_STEPPROF_SAMPLE"] = "1"
    try:
        net.fit(iter(batches), epochs=1)
    finally:
        set_flight_recorder(prev_rec)
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    summary = phase_summary(rec.channel(CHANNEL).items())
    coverage = summary.get("sampled_coverage")
    return {
        "metric": "profiler_overhead_ms",
        "value": round(on_ms, 3),
        "unit": "ms/step stepprof enabled",
        "off_ms": round(off_ms, 3),
        "overhead_ms": round(overhead_ms, 3),
        "overhead_pct": None if overhead_pct is None
        else round(overhead_pct, 2),
        "target_pct": 2.0,
        "phase_coverage": None if coverage is None else round(coverage, 4),
        "phase_share": {k: round(v, 4) for k, v in
                        (summary.get("phase_share") or {}).items()},
        "steps": n_batches,
        "runs": max(1, runs),
    }


def sharded_step_time_ms(hidden: int = 512, features: int = 256,
                         classes: int = 32, batch: int = 64,
                         steps: int = 12, warm: int = 2,
                         dp: Optional[int] = None,
                         min_shard_size: Optional[int] = None) -> Dict:
    """ZeRO-3 sharded-training benchmark (ISSUE 12): steady per-step
    train time through ``parallel.ShardedTrainer`` (params + updater
    state row-sharded over the data axis; reduce-scatter gradients,
    shard-local update, XLA-inserted forward all-gather) vs the
    replicated ``ParallelWrapper`` (full params per device, dense
    all-reduce) at a FIXED global batch on the same mesh — plus the
    memory side of the trade: per-device parameter bytes, which the
    sharded layout holds at ~1/dp of replicated (``param_bytes_ratio``).

    ``train_step_traces`` carries the compile-counter delta across BOTH
    runs: the sharded and replicated paths execute the same jitted
    program from the process-global trace cache (sharding lives in the
    arguments, not the trace), so the whole bench traces ONCE.  On the
    1-core CPU rig the collectives are memcpy loops and sharding is pure
    overhead (``vs_replicated`` > 1 is expected there); the row exists
    to track the trajectory and the memory win, which is
    backend-independent."""
    import jax

    from ..nn.conf.input_type import InputType
    from ..nn.conf.multi_layer import NeuralNetConfiguration
    from ..nn.conf.updaters import Adam
    from ..nn.layers.feedforward import DenseLayer, OutputLayer
    from ..nn.multilayer import MultiLayerNetwork
    from ..observability.registry import default_registry
    from ..parallel import (ParallelWrapper, ShardedTrainer, make_mesh,
                            param_bytes, per_device_param_bytes)

    from ..parallel.mesh import DEFAULT_MIN_SHARD_SIZE
    if min_shard_size is None:
        # track the trainer's default so the row always measures the
        # layout ShardedTrainer actually ships
        min_shard_size = DEFAULT_MIN_SHARD_SIZE
    if dp is None:
        dp = len(jax.devices())

    def build():
        conf = (NeuralNetConfiguration.builder().seed(11)
                .updater(Adam(learning_rate=0.01)).list()
                .layer(DenseLayer(n_out=hidden, activation="tanh"))
                .layer(DenseLayer(n_out=hidden, activation="tanh"))
                .layer(DenseLayer(n_out=hidden, activation="tanh"))
                .layer(OutputLayer(n_out=classes, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(features)).build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(3)
    x = rng.standard_normal((batch, features)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, batch)]

    def traces() -> float:
        c = default_registry().get("training_compile_total")
        return 0.0 if c is None else c.labels("train_step").value

    t_before = traces()
    mesh = make_mesh(dp=dp, tp=1, sp=1)
    results = {}
    nets = []   # keep both nets alive: the shared trace-cache entry is
    # weak-valued, so dropping the first net would free the jitted step
    # and bill the second run a spurious retrace
    for impl in ("replicated", "sharded"):
        net = build()
        nets.append(net)
        tr = ParallelWrapper(net, mesh) if impl == "replicated" else \
            ShardedTrainer(net, mesh, min_shard_size=min_shard_size)
        tr.fit(iter([(x, y, None, None)] * max(1, warm)))   # compile+warm
        t0 = monotonic_s()
        # wrapper.fit closes on a final host sync of the score, so the
        # clock reads device completion, not enqueue
        tr.fit(iter([(x, y, None, None)] * steps))
        ms = (monotonic_s() - t0) / steps * 1e3
        results[impl] = (ms, per_device_param_bytes(net.params),
                         param_bytes(net.params))
    sh_ms, sh_dev_bytes, global_bytes = results["sharded"]
    rep_ms, rep_dev_bytes, _ = results["replicated"]
    return {
        "metric": "sharded_step_time_ms",
        "value": round(sh_ms, 3),
        "unit": f"ms/step (dp={dp} ZeRO-3 sharded)",
        "replicated_ms": round(rep_ms, 3),
        "vs_replicated": round(sh_ms / rep_ms, 3) if rep_ms else None,
        "dp": dp,
        "global_batch": batch,
        "param_bytes_per_device": int(sh_dev_bytes),
        "replicated_param_bytes": int(rep_dev_bytes),
        "param_bytes_ratio": round(sh_dev_bytes / rep_dev_bytes, 4)
        if rep_dev_bytes else None,
        "global_param_bytes": int(global_bytes),
        "min_shard_size": int(min_shard_size),
        "train_step_traces": int(traces() - t_before),
        "steps": steps,
    }


def embedding_grad_exchange_ms(vocabs=(50_000, 500_000),
                               touched_fracs=(0.01, 0.10),
                               dim: int = 16, batch: int = 1024,
                               classes: int = 4, steps: int = 8,
                               warm: int = 2,
                               dp: Optional[int] = None) -> List[Dict]:
    """Sparse-embedding gradient-exchange benchmark (ISSUE 15): steady
    per-step train time of the DENSIFIED index/value exchange (a
    ``sparse_grad=True`` table, ZeRO-3 row-sharded over the mesh
    through ``ShardedTrainer`` — coalesced touched rows, O(capacity·dim)
    collectives, lazy row-space updater) vs the DENSE baseline (the
    replicated ``ParallelWrapper``, whose every step all-reduces the
    full mostly-zero ``[vocab, dim]`` gradient), swept over
    vocab × touched-rows fraction.

    Ids are drawn from a pool of ``frac·vocab`` distinct rows, so the
    sparse path exchanges at most that many rows while the dense path
    always ships the whole table.  On the CPU rig the collectives are
    memcpy loops, which makes the O(vocab) dense volume directly
    visible in step time; the acceptance claim (ISSUE 15: densified
    beats dense at vocab ≥ 50k with ≤10% touched) is ``vs_dense < 1``.
    ``steady_recompiles`` carries the compile-counter delta across the
    timed windows — the zero-steady-state-recompile half of the
    acceptance line (each path compiles its own program up front; the
    timed steps must add none).  SGD keeps the comparison about the
    gradient exchange, not updater-mirror traffic.
    """
    import jax

    from ..nn.conf.multi_layer import NeuralNetConfiguration
    from ..nn.conf.updaters import Sgd
    from ..nn.layers.feedforward import EmbeddingLayer, OutputLayer
    from ..nn.multilayer import MultiLayerNetwork
    from ..observability.registry import default_registry
    from ..parallel import ParallelWrapper, ShardedTrainer, make_mesh

    if dp is None:
        dp = len(jax.devices())

    def build(vocab, sparse):
        lb = (NeuralNetConfiguration.builder().seed(13)
              .updater(Sgd(learning_rate=0.05)).list())
        lb.layer(EmbeddingLayer(n_in=vocab, n_out=dim,
                                sparse_grad=sparse))
        lb.layer(OutputLayer(n_out=classes, activation="softmax",
                             loss="mcxent"))
        return MultiLayerNetwork(lb.build()).init()

    def traces() -> float:
        c = default_registry().get("training_compile_total")
        return 0.0 if c is None else c.labels("train_step").value

    mesh = make_mesh(dp=dp)
    rng = np.random.default_rng(29)
    rows = []
    for vocab in vocabs:
        for frac in touched_fracs:
            pool = rng.choice(vocab, size=max(1, int(frac * vocab)),
                              replace=False)
            ids = pool[rng.integers(0, len(pool), batch)] \
                .reshape(batch, 1).astype(np.int32)
            y = np.eye(classes, dtype=np.float32)[
                rng.integers(0, classes, batch)]
            results = {}
            recompiles = 0.0
            nets = []   # both nets stay alive: the shared trace-cache
            # entries are weak-valued (see sharded_step_time_ms)
            for impl in ("dense", "sparse"):
                net = build(vocab, impl == "sparse")
                nets.append(net)
                tr = ParallelWrapper(net, mesh) if impl == "dense" else \
                    ShardedTrainer(net, mesh, min_shard_size=0)
                tr.fit(iter([(ids, y, None, None)] * max(1, warm)))
                t_steady = traces()
                t0 = monotonic_s()
                # wrapper.fit closes on a final host sync of the score,
                # so the clock reads device completion, not enqueue
                tr.fit(iter([(ids, y, None, None)] * steps))
                results[impl] = (monotonic_s() - t0) / steps * 1e3
                recompiles += traces() - t_steady
            sp_ms, de_ms = results["sparse"], results["dense"]
            rows.append({
                "metric": f"embedding_grad_exchange_ms"
                          f"[v={vocab},t={frac:g}]",
                "value": round(sp_ms, 3),
                "unit": "ms/step (densified index/value exchange, "
                        "row-sharded table)",
                "dense_all_reduce_ms": round(de_ms, 3),
                "vs_dense": round(sp_ms / de_ms, 3) if de_ms else None,
                "densified_wins": bool(sp_ms < de_ms),
                "vocab": int(vocab), "dim": dim,
                "touched_frac": float(frac),
                "touched_rows_max": int(len(pool)),
                "capacity": int(min(batch, vocab)),
                "table_mbytes": round(vocab * dim * 4 / 2**20, 2),
                "dp": dp, "global_batch": batch,
                "steady_recompiles": int(recompiles),
                "steps": steps,
            })
    return rows


def elastic_reshard_ms(hidden: int = 32, features: int = 8,
                       classes: int = 4, n_batches: int = 16,
                       batch: int = 8, save_freq: int = 2,
                       lease_ttl_s: float = 0.4,
                       step_sleep_s: float = 0.05) -> Dict:
    """Elastic-reshard benchmark (ISSUE 13): wall time from a MEMBER
    LOSS (its last heartbeat — the process is gone) to the FIRST clean
    sharded train step on the survivor mesh.  The run is the real
    elastic path end to end: a two-member view over a dp=4 ZeRO-3 mesh,
    the dead member's in-flight barrier round aborted (never a torn
    store), eviction at the next round boundary, the survivor mesh
    rebuilt through ``restore_sharded(mesh=survivors)`` (params +
    updater mirrors re-placed byte-exact at dp=2), then training
    continues — ``restore_ms`` carries the reshard-restore slice of
    that window, ``detect_ms`` the lease-expiry + boundary wait.  The
    train step itself keeps its single process-global trace across the
    topology change (re-LOWERING for the new mesh is part of the
    measured window, as it is in production)."""
    import tempfile

    import jax

    from ..faulttolerance.cluster import (ClusterCoordinator,
                                          ClusterMember, FileLeaseStore)
    from ..nn.conf.input_type import InputType
    from ..nn.conf.multi_layer import NeuralNetConfiguration
    from ..nn.conf.updaters import Adam
    from ..nn.layers.feedforward import DenseLayer, OutputLayer
    from ..nn.multilayer import MultiLayerNetwork
    from ..parallel.distributed import ElasticTrainer
    from ..parallel.mesh import make_mesh
    from ..parallel.sharded import ShardedTrainer

    import time

    if len(jax.devices()) < 4:
        raise RuntimeError("elastic_reshard_ms needs >= 4 devices")

    def build():
        conf = (NeuralNetConfiguration.builder().seed(11)
                .updater(Adam(learning_rate=0.02)).list()
                .layer(DenseLayer(n_out=hidden, activation="tanh"))
                .layer(OutputLayer(n_out=classes, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(features))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(5)
    batches = []
    for _ in range(n_batches):
        x = rng.standard_normal((batch, features)).astype(np.float32)
        y = np.eye(classes, dtype=np.float32)[
            rng.integers(0, classes, batch)]
        batches.append((x, y))

    # prewarm the TRACE (dp=4 executable): the member must die mid-run,
    # not during the first step's cold compile
    warm = build()
    ShardedTrainer(warm, make_mesh(dp=4), min_shard_size=0).fit_batch(
        batches[0])

    workdir = tempfile.mkdtemp(prefix="dl4j-reshard-bench-")
    try:
        store = FileLeaseStore(workdir)
        coord = ClusterCoordinator(store, lease_ttl_s=lease_ttl_s)
        m0 = ClusterMember(store, 0, lease_ttl_s=10.0)
        m0.renew_once()
        net = build()
        st = ShardedTrainer(net, make_mesh(dp=4), min_shard_size=0)
        trainer = ElasticTrainer(
            st, workdir, save_freq=save_freq, member=m0,
            coordinator=coord,
            mesh_factory=lambda w: make_mesh(dp=2 * w),
            barrier_timeout_s=10.0)
        # the doomed member: one lease, never renewed — its "death" is
        # the renew timestamp, its loss is DETECTED when the lease
        # expires under the survivor's barrier/boundary machinery
        store.renew(1, ttl_s=lease_ttl_s)
        t_loss = monotonic_s()
        coord.begin_round(0)

        step_done_s: list = []

        class _Clock:
            def iteration_done(self, model, iteration, epoch):
                step_done_s.append(monotonic_s())

        net.listeners.append(_Clock())

        def feed():
            for b in batches:
                time.sleep(step_sleep_s)
                yield b

        steps = trainer.fit(feed)
        m0.stop()
    finally:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)

    ev = trainer.reshard_events[0] if trainer.reshard_events else None
    first_clean = None
    if ev is not None:
        after = [t for t in step_done_s if t > ev["t"]]
        first_clean = after[0] if after else None
    value = None if (ev is None or first_clean is None) \
        else (first_clean - t_loss) * 1e3
    return {
        "metric": "elastic_reshard_ms",
        "value": None if value is None else round(value, 2),
        "unit": "ms member loss -> first clean sharded step "
                "(survivor mesh)",
        "restore_ms": None if ev is None else round(ev["ms"], 2),
        "detect_ms": None if (ev is None or first_clean is None)
        else round(value - ev["ms"], 2),
        "dp_before": 4, "dp_after": None if ev is None else ev["dp"],
        "world_before": 2,
        "world_after": None if ev is None else ev["world_size"],
        "barrier_aborts": trainer.barrier_aborts,
        "lease_ttl_s": lease_ttl_s, "save_freq": save_freq,
        "steps": steps,
    }


def dispatch_pipeline_ms(depths=(2, 4), n_batches: int = 24,
                         runs: int = 7) -> Dict:
    """Bounded-dispatch pipeline benchmark (ISSUE 18): steady per-step
    train time at ``DL4J_TPU_DISPATCH_DEPTH=1`` (the fully serial
    per-step-sync loop) vs the windowed depths, on two arms chosen to
    bracket the claim:

    - **dispatch-bound** — a model tiny enough that the compiled step is
      microseconds, so the step time IS the host-side dispatch work the
      window overlaps (the regime the pipeline exists for);
    - **compute-bound** — the :func:`profiler_overhead_ms` geometry,
      where the device math dominates and the honest expectation is a
      speedup near 1.0 (the window can only hide host time that exists).

    Same paired design as :func:`obs_overhead_ms`: both arms of a pair
    run back to back per round with alternating order, and the reported
    per-depth speedup is the median of per-round ``depth1/depthN``
    ratios.  The depth is read per fit (``configured_depth``), and it
    lives entirely host-side — flipping it must not retrace, which
    ``train_step_traces`` (the compile-counter delta across every
    post-warm fit) proves on the row itself."""
    from ..nn.conf.input_type import InputType
    from ..nn.conf.multi_layer import NeuralNetConfiguration
    from ..nn.conf.updaters import Adam
    from ..nn.dispatch import ENV_VAR
    from ..nn.layers.feedforward import DenseLayer, OutputLayer
    from ..nn.multilayer import MultiLayerNetwork
    from ..observability.registry import default_registry

    def traces() -> float:
        c = default_registry().get("training_compile_total")
        return 0.0 if c is None else c.labels("train_step").value

    def timed(net, batches, depth: int) -> float:
        prev = os.environ.get(ENV_VAR)
        os.environ[ENV_VAR] = str(depth)
        try:
            t0 = monotonic_s()
            net.fit(iter(batches), epochs=1)
            # fit's epoch-end drain syncs the last score, so the clock
            # reads device completion at every depth, not enqueue
            return (monotonic_s() - t0) / len(batches) * 1e3
        finally:
            if prev is None:
                os.environ.pop(ENV_VAR, None)
            else:
                os.environ[ENV_VAR] = prev

    def arm(hidden: int, features: int, classes: int, batch: int) -> Dict:
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Adam(learning_rate=0.01)).list()
                .layer(DenseLayer(n_out=hidden, activation="tanh"))
                .layer(DenseLayer(n_out=hidden, activation="tanh"))
                .layer(OutputLayer(n_out=classes, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(features)).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(13)
        batches = [(rng.standard_normal((batch, features))
                    .astype(np.float32),
                    np.eye(classes, dtype=np.float32)[
                        rng.integers(0, classes, batch)])
                   for _ in range(n_batches)]
        net.fit(iter(batches[:2]), epochs=1)      # compile + warm
        out = {}
        for depth in depths:
            serial, deep, ratios = [], [], []
            for i in range(max(1, runs)):
                # alternate arm order: the second fit of a pair runs
                # cache-warmer, so a fixed order would bias the ratios
                if i % 2 == 0:
                    s = timed(net, batches, 1)
                    d = timed(net, batches, depth)
                else:
                    d = timed(net, batches, depth)
                    s = timed(net, batches, 1)
                serial.append(s)
                deep.append(d)
                ratios.append(s / d if d > 0 else 1.0)
            out[f"depth1_ms_vs{depth}"] = round(float(np.median(serial)), 3)
            out[f"depth{depth}_ms"] = round(float(np.median(deep)), 3)
            out[f"speedup_depth{depth}"] = round(float(np.median(ratios)), 3)
        return out

    t_before = traces()   # post-warm counter is read inside arm(); the
    # delta therefore counts BOTH arms' one-time compiles and nothing
    # from the depth flips themselves
    dispatch_bound = arm(hidden=16, features=16, classes=4, batch=8)
    compute_bound = arm(hidden=256, features=128, classes=10, batch=128)
    trace_delta = int(traces() - t_before)
    lead = sorted(int(d) for d in depths)[0]
    return {
        "metric": "dispatch_pipeline_ms",
        "value": dispatch_bound[f"depth{lead}_ms"],
        "unit": f"ms/step dispatch-bound arm @ depth={lead}",
        "dispatch_bound": dispatch_bound,
        "compute_bound": compute_bound,
        "depths": [int(d) for d in depths],
        # 2 arms x (warm + paired fits); every fit past the two warmups
        # reuses the warm executable — the depth knob is host-only
        "train_step_traces_total": trace_delta,
        "steady_recompiles": max(0, trace_delta - 2),
        "steps": n_batches,
        "runs": max(1, runs),
    }


# ------------------------------------------------------------------ fleet
class _DevicePacedFn:
    """One compiled program with a fixed per-call pace appended.

    The sleep stands in for the device-step time of a real accelerator:
    on a TPU the host enqueues and goes idle while the device computes,
    so N replicas' steps overlap even on one host core.  On the 1-core
    CPU rig the XLA step occupies the host itself, which would make a
    fleet bench measure core contention instead of the routing tier —
    the pace (a GIL-releasing sleep, zero CPU) restores the
    host-async timing profile the fleet is designed for.  The wrapped
    program still runs for real (outputs stay bit-exact, traces still
    count), and attribute reads (``last_call_traced``) pass through."""

    def __init__(self, fn, pace_s: float):
        self._fn = fn
        self._pace_s = float(pace_s)

    def __call__(self, *args, **kw):
        out = self._fn(*args, **kw)
        time.sleep(self._pace_s)
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


class _DevicePacedModel:
    """Model proxy whose compiled programs carry a fixed device pace.

    Intercepts ``_get_jitted`` (the single seam both the serving slot
    and the generation engine compile through) and returns cached
    :class:`_DevicePacedFn` wrappers — cached so program identity stays
    stable for the engines' trace accounting.  Everything else
    (``params``/``state``/``conf``/``output``/...) forwards to the real
    model."""

    def __init__(self, model, pace_s: float):
        self._model = model
        self._pace_s = float(pace_s)
        self._paced: Dict[str, _DevicePacedFn] = {}

    def _get_jitted(self, kind: str):
        fn = self._paced.get(kind)
        if fn is None:
            fn = _DevicePacedFn(self._model._get_jitted(kind),
                                self._pace_s)
            self._paced[kind] = fn
        return fn

    def __getattr__(self, name):
        return getattr(self._model, name)


def serve_fleet(replica_counts=(1, 2, 4), *, model=None, lm=None,
                pace_ms: float = 12.0, concurrency: int = 32,
                n_requests: int = 384, max_batch: int = 4,
                max_slots: int = 2, new_tokens: int = 24,
                kill_tokens: int = 48, max_seq: int = 64) -> List[Dict]:
    """Serving-fleet bench (ISSUE 20): closed-loop ``/predict`` req/s and
    ``/generate`` decode tokens/s through :class:`serving.ServingFleet`
    at each replica count, with ``vs_one_replica`` ratios (the
    acceptance gate: near-linear — >= 3x at 4 replicas), plus a
    kill-one-replica chaos row whose ``recovery_ms`` is the worst
    migrated session's gap from ``kill()`` to its first token on a
    survivor.  Every replica is device-paced (see
    :class:`_DevicePacedFn`): per-replica throughput is bounded by the
    paced step cadence, not host FLOPs, so the rows measure what the
    fleet tier adds — routing, affinity, migration — at the timing
    profile of real accelerator replicas.  ``steady_recompiles`` rides
    every row (warmed replicas + the process-shared trace cache must
    keep it 0 — including after the kill-phase rejoinless migration)."""
    import threading

    from ..generation import GenerationConfig
    from ..models import LeNet, TransformerLM
    from ..observability import MetricsRegistry
    from ..serving.fleet import ServingFleet

    pace_s = pace_ms / 1e3
    counts = sorted(int(r) for r in replica_counts)
    rows: List[Dict] = []

    # ---- stateless /predict: least-loaded routing over paced replicas
    if model is None:
        model = LeNet().init()
    probe = np.random.default_rng(0).standard_normal(
        _probe_shape(model)).astype(np.float32)
    paced = _DevicePacedModel(model, pace_s)
    base_rps = None
    for r in counts:
        fleet = ServingFleet(paced, n_replicas=r,
                             engine_kw=dict(max_batch_size=max_batch,
                                            queue_limit=1024),
                             registry=MetricsRegistry())
        try:
            fleet.warmup()
            lats, wall, errs = _closed_loop(
                lambda: fleet.predict(probe), concurrency, n_requests)
            lats_ms = np.asarray(lats) * 1e3
            rps = round(len(lats) / wall, 1)
            row = {
                "metric": f"serve_fleet[predict,r={r}]",
                "value": rps, "unit": "req/s", "replicas": r,
                "p50_ms": round(float(np.percentile(lats_ms, 50)), 2),
                "p99_ms": round(float(np.percentile(lats_ms, 99)), 2),
                "requests": len(lats), "errors": errs,
                "concurrency": concurrency, "max_batch": max_batch,
                "pace_ms": pace_ms,
                "steady_recompiles": fleet.stats()["steady_recompiles"],
            }
        finally:
            fleet.shutdown()
        if base_rps is None:
            base_rps = rps
        else:
            row["vs_one_replica"] = round(rps / base_rps, 2) \
                if base_rps else None
        rows.append(row)

    # ---- session-affine /generate: decode tokens/s + kill-one chaos
    if lm is None:
        lm = TransformerLM(vocab_size=64, seq_len=max_seq, embed=32,
                           n_layers=2, n_heads=2).init()
    paced_lm = _DevicePacedModel(lm, pace_s)
    vocab = lm.conf.layers[-1].n_out
    rng = np.random.default_rng(1)
    sessions = max_slots * counts[-1]     # fills every slot at max r
    prompts = [rng.integers(1, vocab, 6).tolist() for _ in range(sessions)]
    base_tps = None
    fleet = None
    for r in counts:
        fleet = ServingFleet(
            paced_lm, n_replicas=r,
            generation=GenerationConfig(max_slots=max_slots,
                                        max_seq=max_seq,
                                        queue_limit=4096),
            registry=MetricsRegistry())
        try:
            for rep in fleet.replicas:
                rep.engine.generation.warmup()
            results = [None] * sessions

            def _gen(i):
                results[i] = fleet.generate(
                    prompts[i], max_new_tokens=new_tokens,
                    temperature=0.0, timeout=300.0)

            threads = [threading.Thread(target=_gen, args=(i,))
                       for i in range(sessions)]
            t0 = monotonic_s()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = monotonic_s() - t0
            total = sum(len(res.tokens) for res in results
                        if res is not None)
            tps = round(total / wall, 1)
            row = {
                "metric": f"serve_fleet[decode,r={r}]",
                "value": tps, "unit": "tokens/sec", "replicas": r,
                "sessions": sessions, "new_tokens": new_tokens,
                "tokens": total, "max_slots": max_slots,
                "pace_ms": pace_ms,
                "steady_recompiles": fleet.stats()["steady_recompiles"],
            }
            if base_tps is None:
                base_tps = tps
            else:
                row["vs_one_replica"] = round(tps / base_tps, 2) \
                    if base_tps else None
            rows.append(row)
        finally:
            if r != counts[-1]:
                fleet.shutdown()

    # ---- chaos: kill one replica mid-decode on the widest fleet
    try:
        router = fleet.router
        handles = [router.open_session(p, max_new_tokens=kill_tokens,
                                       temperature=0.0)
                   for p in prompts]
        tok_times = [[] for _ in handles]
        stream_errs: List[str] = []

        def _consume(i, sess):
            for ev in router.events(sess, timeout=120.0):
                if "token" in ev:
                    tok_times[i].append(monotonic_s())
                if "error" in ev:
                    stream_errs.append(str(ev["error"]))

        threads = [threading.Thread(target=_consume, args=(i, s))
                   for i, s in enumerate(handles)]
        for t in threads:
            t.start()
        deadline = monotonic_s() + 60.0
        while monotonic_s() < deadline:
            if all(len(s.mirror["tokens"]) >= 1 for s in handles):
                break
            time.sleep(0.002)
        victim = handles[0].replica.id
        t_kill = monotonic_s()
        fleet.kill(victim)
        for t in threads:
            t.join(timeout=180)
        migrated = [i for i, s in enumerate(handles) if s.epoch > 0]
        recovery_ms = None
        if migrated:
            recovery_ms = round(max(
                next(t for t in tok_times[i] if t > t_kill) - t_kill
                for i in migrated
                if any(t > t_kill for t in tok_times[i])) * 1e3, 1)
        rows.append({
            "metric": "serve_fleet[recovery]",
            "value": recovery_ms, "unit": "ms kill->first survivor token",
            "replicas": counts[-1], "killed": victim,
            "migrated": len(migrated), "sessions": sessions,
            "completed": sum(len(ts) == kill_tokens for ts in tok_times),
            "errors": len(stream_errs),
            "steady_recompiles": fleet.stats()["steady_recompiles"],
        })
    finally:
        fleet.shutdown()
    return rows


def _probe_shape(model):
    try:
        return tuple(model.conf.input_type.shape(-1)[1:])
    except Exception:
        return (784,)
