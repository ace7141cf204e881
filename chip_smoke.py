#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls, at the full width of GPT-2 small (depth as published too) and of
ResNet50-224, with weights and data made from a fixed seed:

  lm     ``models.TransformerLM`` trained with ``fit`` in bfloat16 under
         ``attn_impl="auto"``: every loss finite, the first-step loss within
         tolerance of the same model built with ``attn_impl="reference"``,
         and the Pallas flash forward and both backward kernels present in
         the lowered train step (a silent fall to the reference path fails).
  serve  a ``GenerationEngine`` over that model (paged KV, shared prefix,
         concurrent greedy requests of different lengths, one streamed):
         tokens checked against the full-forward argmax oracle the tier-1
         tests use, zero steady recompiles; then one request through
         ``FleetServer``/``FleetClient`` with one replica.
  conv   ``models.available_bench_model`` (ResNet50-224, bf16, batch 256):
         ``fit`` steps and one short ``fit_on_device`` epoch.

``--chips 4`` runs instead, and only: the same LM under
``ShardedTrainer(model, make_mesh(dp=4))`` and the one-chip ``fit`` on the
same seed and batches that it is compared with.

The LAST line on standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, built from
``jax.devices()``; everything else worth seeing goes on earlier lines, and
logging and warnings go to stderr.  Any failed phase, or no TPU, exits
non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import logging
import os
import sys
import threading
import time
import traceback

import numpy as np

SEED = 0

# GPT-2 small: 12 blocks, 12 heads of 64, context 1024; vocabulary padded to
# a lane multiple as deployments do.  ~135 M parameters with the untied head.
LM = dict(vocab_size=32768, seq_len=1024, embed=768, n_layers=12,
          n_heads=12)
# 8 x 1024 tokens a step: the compiler's memory analysis for the v5e puts
# the flash step at 9.3 GB of its 16 GB (7.8 GB of it activations).  The
# reference-attention twin needs about twice the activations, so the two
# are compared on the first LM_COMPARE_BATCH rows.
LM_BATCH = 8
LM_COMPARE_BATCH = 4
LM_STEPS = 3
# both sides compute in bfloat16 from identical f32 masters and differ only
# in the attention arithmetic (or in how the batch is split over chips); the
# loss averages over thousands of tokens.  Seen on the v5e in PR 22: 4e-6
# (auto vs reference) and up to 2e-5 (dp=4 vs one chip).
LOSS_RTOL = 2e-3

SERVE = dict(max_slots=8, max_seq=1024, block_size=16)
SERVE_NEW_TOKENS = 12
# The engine attends through the paged pool with the reference math and the
# oracle through the full causal forward (the flash kernel on a TPU), both
# on the f32 masters with the backend's default matmul precision (bf16
# passes on a TPU).  A freshly initialised 32k-way head is nearly flat, so
# two programs may order a near tie differently: a token counts as right
# when the oracle puts it within TIE_NATS of its own argmax.  A wrong cache
# or position misses by the spread of the head, about a nat.  Seen on the
# v5e in PR 22: 59 of 60 tokens the argmax itself, the other 0.0014 nats off.
TIE_NATS = 0.01

CONV = dict(batch=256, image=224, fit_steps=2, epoch_batches=3)


def say(*parts) -> None:
    print(*parts, flush=True)


def accelerators():
    """``jax.devices()`` when they are TPU chips, else None."""
    import jax
    devices = jax.devices()
    return devices if devices[0].platform == "tpu" else None


def build_conv(n_examples: int):
    from deeplearning4j_tpu.models import available_bench_model
    return available_bench_model(batch=n_examples, image=CONV["image"])


def lm_batches(n_batches: int, rows: int):
    """Token batches ``(x, y)`` of next-token pairs from the seed."""
    rng = np.random.default_rng(SEED)
    ids = rng.integers(0, LM["vocab_size"],
                       (n_batches, rows, LM["seq_len"] + 1)).astype(np.int32)
    return [(b[:, :-1], b[:, 1:]) for b in ids]


def build_lm(attn_impl: str):
    from deeplearning4j_tpu.models import TransformerLM
    return TransformerLM(attn_impl=attn_impl, sparse_labels=True,
                         compute_dtype="bfloat16", **LM).init()


def peak_gb(devices) -> str:
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        return "not reported by this backend"
    return " ".join(f"{s['peak_bytes_in_use'] / 2 ** 30:.2f}" for s in stats)


def train_step_traces() -> float:
    from deeplearning4j_tpu.observability.registry import default_registry
    counter = default_registry().get("training_compile_total")
    return 0.0 if counter is None else counter.labels("train_step").value


def flash_kernels_in_train_step(batch_rows: int):
    """Names of the flash kernels in the lowered train step that ran on
    ``batch_rows``-row batches, read from the program's own text."""
    from deeplearning4j_tpu.nn import compile_cache
    from deeplearning4j_tpu.ops.flash_attention import KERNEL_NAMES
    found = set()
    for _key, entry in compile_cache.iter_trace_cache():
        if entry.name != "train_step":
            continue
        for spec in entry.audit_specs():
            if spec[0][4].shape[0] != batch_rows:
                continue
            text = entry.audit_lower(spec).as_text()
            if "tpu_custom_call" in text:
                found.update(n for n in KERNEL_NAMES if n in text)
    return sorted(found)


def timed_fit(fit, batches):
    """``fit`` each batch, closing every clock on the host fetch of the
    loss.  Returns (losses, seconds); the first entry includes compile."""
    losses, seconds = [], []
    for x, y in batches:
        t0 = time.perf_counter()
        losses.append(float(fit(x, y).get_score()))
        seconds.append(time.perf_counter() - t0)
    return losses, seconds


def check_losses(losses, what: str) -> None:
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{what}: loss not finite: {losses}")


def check_close(a: float, b: float, what: str) -> None:
    if abs(a - b) > LOSS_RTOL * abs(b):
        raise AssertionError(
            f"{what}: {a!r} vs {b!r} differ by more than {LOSS_RTOL:.0e} "
            "relative")


# ------------------------------------------------------------------ phases
def phase_lm(devices):
    """Returns the trained LM for the serve phase."""
    from deeplearning4j_tpu.nn.layers.attention import auto_attention_impl
    on_tpu = devices[0].platform == "tpu"
    t, head_dim = LM["seq_len"], LM["embed"] // LM["n_heads"]
    chosen = auto_attention_impl(t, t, head_dim, masked=False)
    say(f"lm: attn_impl='auto' chose '{chosen}' at t={t} head_dim={head_dim}")
    if on_tpu and chosen != "flash":
        raise AssertionError("auto did not choose the flash kernel on a TPU")

    batches = lm_batches(LM_STEPS, LM_BATCH)
    first = (batches[0][0][:LM_COMPARE_BATCH], batches[0][1][:LM_COMPARE_BATCH])
    ref = build_lm("reference")
    (ref_loss,), (ref_s,) = timed_fit(ref.fit, [first])
    del ref
    gc.collect()

    lm = build_lm("auto")
    say(f"lm: {lm.num_params() / 1e6:.1f} M parameters")
    (loss0,), (s0,) = timed_fit(lm.fit, [first])
    say(f"lm: first-step loss on {LM_COMPARE_BATCH} rows: auto {loss0:.5f} "
        f"reference {ref_loss:.5f} rel diff "
        f"{abs(loss0 - ref_loss) / abs(ref_loss):.2e} (bfloat16 compute); "
        f"first step incl. compile {s0:.1f} s, reference {ref_s:.1f} s")
    check_losses([loss0, ref_loss], "lm first step")
    check_close(loss0, ref_loss, "lm first-step loss, auto vs reference")

    losses, seconds = timed_fit(lm.fit, batches)
    say(f"lm: {LM_STEPS} fit steps of {LM_BATCH}x{t} tokens: losses "
        f"{[round(v, 4) for v in losses]}; first incl. compile "
        f"{seconds[0]:.1f} s, then {[round(v, 3) for v in seconds[1:]]} s")
    check_losses(losses, "lm fit")

    kernels = flash_kernels_in_train_step(LM_BATCH)
    say(f"lm: flash kernels in the lowered train step: {kernels}")
    if chosen == "flash" and len(kernels) != 3:
        raise AssertionError(
            "the lowered train step lacks the flash forward or a backward "
            f"kernel: found {kernels}")
    say(f"lm: memory_stats peak GB {peak_gb(devices)}")
    return lm


def serve_requests():
    """Greedy prompts of different lengths: four share a header of four
    whole KV blocks (their suffix buckets still fit behind it), and one
    cold prompt fills most of the context."""
    rng = np.random.default_rng(SEED + 1)
    vocab, max_seq = LM["vocab_size"], SERVE["max_seq"]
    header = rng.integers(0, vocab, 4 * SERVE["block_size"]).tolist()
    room = max_seq - len(header) - SERVE_NEW_TOKENS
    tails = [max(1, int(room * f)) for f in (0.01, 0.05, 0.2, 0.45)]
    prompts = [header + rng.integers(0, vocab, n).tolist() for n in tails]
    prompts.append(rng.integers(0, vocab, int(room * 0.9)).tolist())
    return prompts


def oracle_gaps(lm, prompt, tokens):
    """How far (nats) the full-forward oracle puts each generated token
    below its own argmax — 0.0 where the token IS the argmax.  One causal
    forward over prompt + tokens, padded to ``max_seq`` (padding sits after
    every position read)."""
    hist = list(prompt) + list(tokens)
    x = np.zeros((1, SERVE["max_seq"]), np.int32)
    x[0, :len(hist)] = hist
    lo = len(prompt) - 1
    probs = np.asarray(lm.output(x)[0, lo:lo + len(tokens)], np.float64)
    logp = np.log(np.maximum(probs, 1e-300))
    return logp.max(axis=-1) - logp[np.arange(len(tokens)), tokens]


def check_tokens(lm, prompt, tokens, what: str):
    if len(tokens) != SERVE_NEW_TOKENS:
        raise AssertionError(f"{what}: {len(tokens)} tokens, wanted "
                             f"{SERVE_NEW_TOKENS}")
    gaps = oracle_gaps(lm, prompt, tokens)
    if not (gaps <= TIE_NATS).all():
        raise AssertionError(
            f"{what}: tokens {tokens} stray from the oracle by "
            f"{gaps.round(4).tolist()} nats (tolerance {TIE_NATS})")
    return int((gaps == 0.0).sum()), float(gaps.max())


def device_ids(tree) -> list:
    import jax
    return sorted({d.id for leaf in jax.tree_util.tree_leaves(tree)
                   for d in leaf.devices()})


def phase_serve(lm, devices):
    from deeplearning4j_tpu.generation import (GenerationConfig,
                                               GenerationEngine)
    from deeplearning4j_tpu.serving.fleet import (FleetClient, FleetServer,
                                                  ServingFleet)
    config = GenerationConfig(**SERVE)
    prompts = serve_requests()
    say("serve: oracle comparison in float32 parameters and activations, "
        "the backend's default matmul precision, tie tolerance "
        f"{TIE_NATS} nats")
    engine = GenerationEngine.for_model(lm, config)
    try:
        t0 = time.perf_counter()
        warmed = engine.warmup()
        say(f"serve: warmup compiled {warmed} programs (prefill buckets "
            f"{engine.buckets} + decode) in {time.perf_counter() - t0:.1f} s")
        handles = [engine.submit(p, max_new_tokens=SERVE_NEW_TOKENS)
                   for p in prompts[1:]]
        streamed = [ev["token"] for ev in engine.stream(
            prompts[0], timeout=300.0, max_new_tokens=SERVE_NEW_TOKENS)
            if "token" in ev]
        results = [streamed] + [h.future.result(timeout=300).tokens
                                for h in handles]
        exact = total = 0
        worst = 0.0
        for prompt, tokens in zip(prompts, results):
            n, gap = check_tokens(lm, prompt, tokens,
                                  f"serve prompt of {len(prompt)}")
            exact, total, worst = exact + n, total + len(tokens), max(worst,
                                                                      gap)
        status = engine.status()
        say(f"serve: {len(prompts)} concurrent greedy requests, prompt "
            f"lengths {[len(p) for p in prompts]}: {exact}/{total} tokens "
            f"are the oracle's argmax, the rest within {worst:.4f} nats; "
            f"prefix hits {status['kv']['prefix_hits']}, prefill tokens "
            f"saved {status['kv']['prefix_tokens_saved']}, steady "
            f"recompiles {status['steady_recompiles']}")
        if status["steady_recompiles"] != 0:
            raise AssertionError("the engine recompiled after warm-up")
        if status["kv"]["prefix_hits"] < 3:
            raise AssertionError("shared-prefix blocks were not adopted: "
                                 f"{status['kv']}")
        say(f"serve: engine params on devices {device_ids(lm.params)}, KV "
            f"pool on {device_ids(engine.ring.caches)}, pool "
            f"{status['cache_bytes'] / 2 ** 30:.2f} GB")
    finally:
        engine.shutdown()

    fleet = ServingFleet(lm, n_replicas=1, generation=config,
                         start_health=False)
    server = FleetServer(fleet).start()
    client = FleetClient(f"http://127.0.0.1:{server.port}", timeout=300.0)
    try:
        t0 = time.perf_counter()
        reply = client.generate(prompts[-1], max_new_tokens=SERVE_NEW_TOKENS)
        n, gap = check_tokens(lm, prompts[-1], reply["tokens"], "fleet")
        for replica in fleet.replicas:
            gen = replica.engine.generation
            say(f"serve: fleet replica {replica.id}: params on devices "
                f"{device_ids(replica.engine.slot.model.params)}, KV pool "
                f"on {device_ids(gen.ring.caches)}")
        say(f"serve: one request through FleetServer/FleetClient, one "
            f"replica: {n}/{len(reply['tokens'])} argmax, worst gap "
            f"{gap:.4f} nats, same tokens as the engine: "
            f"{reply['tokens'] == results[-1]}, "
            f"{time.perf_counter() - t0:.1f} s")
    finally:
        client.close()
        server.stop()           # also shuts the fleet's engines down
    say(f"serve: memory_stats peak GB {peak_gb(devices)}")


def phase_conv(devices):
    import jax.numpy as jnp
    batch, nb = CONV["batch"], CONV["epoch_batches"]
    model, (x, y) = build_conv(batch * nb)
    steps = [(x[i * batch:(i + 1) * batch], y[i * batch:(i + 1) * batch])
             for i in range(CONV["fit_steps"])]
    losses, seconds = timed_fit(model.fit, steps)
    say(f"conv: {len(steps)} fit steps at batch {batch}: losses "
        f"{[round(v, 4) for v in losses]}; first incl. compile "
        f"{seconds[0]:.1f} s, then {[round(v, 3) for v in seconds[1:]]} s")
    check_losses(losses, "conv fit")
    it0 = model.iteration
    t0 = time.perf_counter()
    # the device-resident dataset in the compute dtype, as bench.py holds it
    model.fit_on_device(jnp.asarray(x, jnp.bfloat16), jnp.asarray(y),
                        batch_size=batch, epochs=1)
    loss = float(model.get_score())
    say(f"conv: fit_on_device epoch of {nb} batches: loss {loss:.4f}, "
        f"{time.perf_counter() - t0:.1f} s incl. compile")
    check_losses([loss], "conv fit_on_device")
    if model.iteration - it0 != nb:
        raise AssertionError(f"fit_on_device took {model.iteration - it0} "
                             f"steps, wanted {nb}")
    say(f"conv: memory_stats peak GB {peak_gb(devices)}")


def phase_sharded(devices):
    """The LM under ZeRO-3 on a dp=4 mesh against the one-chip fit."""
    import jax
    from deeplearning4j_tpu.parallel import ShardedTrainer, make_mesh
    if len(devices) != 4:
        raise AssertionError(f"--chips 4 needs four chips, found "
                             f"{len(devices)}")
    batches = lm_batches(LM_STEPS, LM_BATCH)

    traces0 = train_step_traces()
    one = build_lm("auto")
    state_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(
        (one.params, one.opt_state)))
    losses1, seconds1 = timed_fit(one.fit, batches)
    traces1 = train_step_traces()
    say(f"sharded: one-chip fit losses {[round(v, 4) for v in losses1]}, "
        f"first step incl. compile {seconds1[0]:.1f} s, train-step traces "
        f"{traces1 - traces0:.0f}")
    check_losses(losses1, "one-chip fit")
    del one
    gc.collect()

    net = build_lm("auto")
    trainer = ShardedTrainer(net, make_mesh(dp=4))
    jax.block_until_ready((net.params, net.opt_state))
    share = trainer.per_device_param_bytes() / trainer.global_param_bytes()
    in_use = [d.memory_stats() for d in devices]
    say(f"sharded: parameters + optimizer state {state_bytes / 2 ** 30:.3f} "
        f"GB whole; per-device parameter share {share:.3f}")
    if not 0.24 < share < 0.30:
        raise AssertionError(f"per-device parameter share {share:.3f} is "
                             "not about a quarter")
    if all(in_use):
        held = [s["bytes_in_use"] / state_bytes for s in in_use]
        say("sharded: bytes in use per device after placement, as a share "
            f"of the whole state: {[round(v, 3) for v in held]}")
        if not all(0.2 < v < 0.45 for v in held):
            raise AssertionError("state is not spread at about a quarter "
                                 f"per device: {held}")
    losses4, seconds4 = [], []
    traces = [train_step_traces()]
    for batch in batches:
        ls, ss = timed_fit(trainer.fit, [batch])
        losses4 += ls
        seconds4 += ss
        traces.append(train_step_traces())
    say(f"sharded: dp=4 fit losses {[round(v, 4) for v in losses4]}, first "
        f"step incl. compile {seconds4[0]:.1f} s, then "
        f"{[round(v, 3) for v in seconds4[1:]]} s; train-step traces per "
        f"step {[int(b - a) for a, b in zip(traces, traces[1:])]}")
    check_losses(losses4, "sharded fit")
    for i, (a, b) in enumerate(zip(losses4, losses1)):
        check_close(a, b, f"step {i} loss, dp=4 vs one chip")
    if traces[-1] != traces[1]:
        raise AssertionError("the sharded step retraced after its first step")
    kernels = flash_kernels_in_train_step(LM_BATCH)
    say(f"sharded: flash kernels in the lowered train steps: {kernels}")
    if devices[0].platform == "tpu" and len(kernels) != 3:
        raise AssertionError(f"flash kernels missing: {kernels}")
    say(f"sharded: memory_stats peak GB per device {peak_gb(devices)}")


def preamble(devices) -> None:
    import importlib.metadata as md

    import jax
    import jaxlib
    from deeplearning4j_tpu import persistent_cache_status
    from deeplearning4j_tpu.observability.profiler import peak_device_flops
    from deeplearning4j_tpu.utils import native
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    say(f"versions: jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu}")
    say(f"device: platform {devices[0].platform} kind "
        f"'{devices[0].device_kind}' count {len(devices)}")
    peak = peak_device_flops()
    say(f"profiler peaks table: {peak and peak / 1e12} bf16 TFLOP/s for "
        "these devices")
    if devices[0].platform == "tpu" and peak is None:
        raise AssertionError(f"device kind '{devices[0].device_kind}' "
                             "matches no row of the profiler's peaks table")
    say(f"compile cache at start: {persistent_cache_status()}")
    t0 = time.time()
    live, path = native.available(), native.library_path()
    built = "-" if path is None else os.path.getmtime(path) >= t0 - 1
    say(f"native library: available {live}, path {path}, built from "
        f"native_src.cpp by this run: {built}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the ShardedTrainer path and the "
                             "one-chip fit it is compared with")
    args = parser.parse_args(argv)
    logging.basicConfig(stream=sys.stderr, level=logging.WARNING)

    devices = accelerators()
    if devices is None:
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 3
    try:
        import deeplearning4j_tpu  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the program is not beside this script: {e}",
              file=sys.stderr)
        return 2

    t_start = time.perf_counter()
    threads_before = set(threading.enumerate())
    phase = "preamble"
    try:
        preamble(devices)
        if args.chips == 4:
            phase = "sharded"
            phase_sharded(devices)
        else:
            phase = "lm"
            lm = phase_lm(devices)
            phase = "serve"
            phase_serve(lm, devices)
            del lm
            gc.collect()
            phase = "conv"
            phase_conv(devices)
        phase = "epilogue"
        from deeplearning4j_tpu import persistent_cache_status
        from deeplearning4j_tpu.observability import startup_report
        say(f"compile cache at end: {persistent_cache_status()}")
        say(f"start-up by parts: {startup_report()}")
        # nothing the phases started may outlive them: a thread that
        # prints after the last line would break it
        left = [t for t in threading.enumerate() if t not in threads_before]
        for t in left:
            t.join(timeout=5)
        left = [t.name for t in left if t.is_alive()]
        if left:
            raise AssertionError(f"threads still running: {left}")
    except Exception:
        traceback.print_exc(file=sys.stderr)
        say(f"phase {phase}: FAILED (traceback on stderr)")
        return 1
    say(f"all phases passed in {time.perf_counter() - t_start:.0f} s")
    import jax
    devices = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
