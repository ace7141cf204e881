"""Paged KV cache + shared-prefix prefill (ISSUE 19; the dense
``SlotRing`` and its ``DL4J_TPU_KV_PAGED=0`` escape hatch were removed
in ISSUE 20, so the dense-vs-paged parity pins live on as paged-only
regressions).

The acceptance spine:

* bit parity: greedy token streams through the paged block-pool cache
  are IDENTICAL to the naive full-forward oracle, the whole mixed
  greedy+sampled workload is invariant to block geometry, and streams
  match the per-version greedy oracles across a mid-flight hot-swap
  migration (re-prefilled through the paged path);
* the two-slot COW aliasing regression: a request appending into a
  partially-filled shared prefix block copies first — a later request
  adopting the same shared block still reads the ORIGINAL tokens' K/V;
* allocator honesty: lowest-free-block allocation, vacate-time release,
  trash-block writability invariant, pool-exhaustion starvation that
  fails the starved request loudly and leaves the engine serving;
* int8 KV (``PrecisionPolicy.kv_dtype``): greedy parity within
  tolerance at roughly half the cache bytes;
* zero steady recompiles across a mixed paged workload, and the
  retired ``DL4J_TPU_KV_PAGED`` env var being ignored (paged is the
  only cache organization).
"""
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.data.shapes import suffix_prefill_buckets
from deeplearning4j_tpu.generation import (GenerationConfig,
                                           GenerationEngine,
                                           StaticSlotSource)
from deeplearning4j_tpu.generation.cache import PagedKV
from deeplearning4j_tpu.models import TransformerLM

VOCAB = 17


@pytest.fixture(scope="module")
def lm():
    return TransformerLM(vocab_size=VOCAB, seq_len=32, embed=16,
                         n_layers=2, n_heads=2).init()


def naive_greedy(net, history, n):
    hist = [int(t) for t in history]
    out = []
    for _ in range(n):
        probs = np.asarray(net.output(np.asarray([hist], np.int32)))
        tok = int(probs[0, len(hist) - 1].argmax())
        out.append(tok)
        hist.append(tok)
    return out


def wait_until(pred, timeout_s=30.0, interval_s=0.005):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval_s)
    return pred()


def run_requests(engine, requests):
    """Submit all, then collect — exercises concurrent slot residency.
    Per-request determinism is the (seed, token_index) RNG contract, so
    batch composition cannot perturb the comparison."""
    handles = [engine.submit(p, **kw) for p, kw in requests]
    return [h.future.result(timeout=120).tokens for h in handles]


REQUESTS = [
    ([3, 1, 4, 1, 5], dict(max_new_tokens=8, seed=11)),
    ([9, 2, 6], dict(max_new_tokens=8, temperature=0.7, top_k=5, seed=42)),
    ([5, 3, 5, 8, 9, 7, 9, 3], dict(max_new_tokens=6, temperature=1.1,
                                    top_p=0.8, seed=7)),
    ([2, 7, 1], dict(max_new_tokens=8, temperature=0.4, seed=13)),
]


# ----------------------------------------------------------- bit parity
class TestPagedParity:
    def test_paged_streams_pin_oracle_and_block_geometry(self, lm):
        """THE parity gate, paged-only since the dense ring's removal:
        greedy streams are bit-identical to the naive full-forward
        oracle, and the whole mixed greedy+sampled workload is invariant
        to block geometry (block size / slot count change WHERE K/V
        lives, never the tokens) across enough concurrent requests to
        exercise block allocation, trash-lane padding and the
        written-prefix mask tail."""
        a = GenerationEngine.for_model(
            lm, GenerationConfig(max_slots=4, max_seq=32, block_size=4))
        try:
            want = run_requests(a, REQUESTS)
            assert a.steady_recompiles == 0
        finally:
            a.shutdown()
        for (prompt, kw), toks in zip(REQUESTS, want):
            if not kw.get("temperature"):          # greedy requests
                assert toks == naive_greedy(lm, prompt, len(toks))
        b = GenerationEngine.for_model(
            lm, GenerationConfig(max_slots=2, max_seq=32, block_size=8))
        try:
            got = run_requests(b, REQUESTS)
            assert b.steady_recompiles == 0
        finally:
            b.shutdown()
        assert got == want

    def test_prefix_sharing_streams_stay_bit_identical(self, lm):
        """Sharing is a pure prefill-work optimization: with a common
        prompt header registered by the first request, later requests
        adopt its blocks and prefill only their suffix — and every
        stream still matches the sharing-disabled engine bit for bit."""
        header = [3, 1, 4, 1, 5, 9, 2, 6]       # two full 4-token blocks
        reqs = [(header + tail, dict(max_new_tokens=6, temperature=0.6,
                                     seed=100 + i))
                for i, tail in enumerate(([7], [8, 2], [9, 9, 1], [4]))]
        cold = GenerationEngine.for_model(
            lm, GenerationConfig(max_slots=2, max_seq=32,
                                 block_size=4, prefix_sharing=False))
        try:
            want = [cold.generate(p, **kw).tokens for p, kw in reqs]
        finally:
            cold.shutdown()
        shared = GenerationEngine.for_model(
            lm, GenerationConfig(max_slots=2, max_seq=32,
                                 block_size=4, prefix_sharing=True))
        try:
            got = [shared.generate(p, **kw).tokens for p, kw in reqs]
            kv = shared.status()["kv"]
            assert kv["prefix_hits"] == 3        # every request after #1
            assert kv["prefix_tokens_saved"] > 0
            assert shared.steady_recompiles == 0
        finally:
            shared.shutdown()
        assert got == want

    def test_cow_two_slot_aliasing_regression(self, lm):
        """The COW pin: request B appends into a PARTIALLY-filled
        shared block (6-token prompt = one full + half a 4-token block),
        request C adopts the same shared prefix afterwards.  Without
        copy-on-write B's first decode write lands in the registered
        block and C gathers B's K/V — caught here as a stream diverging
        from the sharing-disabled reference."""
        prompt_a = [3, 1, 4, 1, 5, 9]            # 1 full block + 2-token tail
        reqs = [
            (prompt_a, dict(max_new_tokens=6, seed=1)),
            (prompt_a + [2, 6, 5, 3], dict(max_new_tokens=6, seed=2)),
            (prompt_a, dict(max_new_tokens=6, temperature=0.5, seed=3)),
            (prompt_a + [8], dict(max_new_tokens=6, seed=4)),
        ]
        cold = GenerationEngine.for_model(
            lm, GenerationConfig(max_slots=2, max_seq=32,
                                 block_size=4, prefix_sharing=False))
        try:
            want = [cold.generate(p, **kw).tokens for p, kw in reqs]
        finally:
            cold.shutdown()
        shared = GenerationEngine.for_model(
            lm, GenerationConfig(max_slots=2, max_seq=32,
                                 block_size=4, prefix_sharing=True))
        try:
            got = [shared.generate(p, **kw).tokens for p, kw in reqs]
            kv = shared.status()["kv"]
            assert kv["cow_copies"] >= 1         # the partial tail was COWed
            assert kv["prefix_hits"] >= 2
            events = [t["event"] for t in shared.ring.trail()]
            assert "cow" in events and "shared_hit" in events
        finally:
            shared.shutdown()
        assert got == want

    def test_migration_reprefills_through_paged_path(self, lm, monkeypatch):
        """Hot-swap during active paged decode: every sequence migrates
        at a step boundary by re-prefilling its own history through the
        paged path — v1-era tokens match the old net's greedy oracle,
        v2-era tokens the new net's continued from the v1 history, and
        the swap costs zero steady recompiles.  The prefix registry is
        invalidated (old-version K/V must never be adopted)."""
        import jax

        net_b = lm.clone()
        net_b.params = jax.tree_util.tree_map(lambda a: a * 1.07,
                                              net_b.params)
        src = StaticSlotSource(lm)
        eng = GenerationEngine(
            src, GenerationConfig(max_slots=2, max_seq=32, block_size=4))
        # deterministic mid-flight swap: park the engine INSIDE its 3rd
        # v1 decode step, swap while it's parked, then let the step
        # finish (still old weights — the engine resolved the model at
        # tick start); the NEXT tick observes the new version and
        # migrates.  A wall-clock wait_until here raced: 15 warm decode
        # ticks can outrun the test thread under full-suite load.
        parked, resume = threading.Event(), threading.Event()
        calls = {"n": 0}
        orig = lm._get_jitted

        def gated(kind):
            fn = orig(kind)
            if kind != "paged_decode":
                return fn

            def stepped(*a, **kw):
                calls["n"] += 1
                if calls["n"] == 3:
                    parked.set()
                    resume.wait(60)
                return fn(*a, **kw)
            return stepped

        try:
            eng.warmup()
            # seed the registry so invalidation has something to drop
            eng.generate([3, 1, 4, 1, 5], max_new_tokens=2, timeout=60)
            assert eng.ring.stats()["blocks_registered"] > 0
            monkeypatch.setattr(lm, "_get_jitted", gated)
            req = eng.submit([9, 2, 6], max_new_tokens=16, seed=5)
            assert parked.wait(60)
            src.swap(net_b)                       # mid-flight, engine parked
            resume.set()
            res = req.future.result(timeout=120)
            toks, vers = res.tokens, res.versions
            assert len(toks) == 16
            assert vers == sorted(vers)
            k = vers.index(2) if 2 in vers else len(toks)
            assert 0 < k < len(toks)              # swap landed mid-flight
            assert toks[:k] == naive_greedy(lm, [9, 2, 6], k)
            assert toks[k:] == naive_greedy(net_b, [9, 2, 6] + toks[:k],
                                            len(toks) - k)
            assert eng.steady_recompiles == 0
            assert any(t["event"] == "migrate" and t["request"] == req.id
                       for t in eng.ring.trail())
        finally:
            eng.shutdown()


# ------------------------------------------------------------- allocator
class TestPagedAllocator:
    def test_lowest_free_alloc_release_and_trail(self, lm):
        kv = PagedKV(lm.conf, max_slots=2, max_seq=32, block_size=8,
                     prefix_sharing=False)
        assert kv.blocks_per_slot == 4
        total_free = kv.blocks_free
        assert total_free == kv.n_blocks - 1      # trash block reserved
        s = kv.acquire("req-a")
        assert all(b == PagedKV.TRASH for b in kv.tables[s])
        assert kv.ensure_blocks(s, "req-a", 1)
        assert kv.tables[s, 0] == 1               # lowest free first
        assert kv.ensure_blocks(s, "req-a", 9)    # spills into 2nd block
        assert kv.tables[s, 1] == 2
        kv.check_writable(s)                      # private block: fine
        assert kv.blocks_free == total_free - 2
        events = [t["event"] for t in kv.trail()]
        assert "block_alloc" in events
        kv.release(s)
        assert kv.blocks_free == total_free       # vacate releases all
        assert any(t["event"] == "block_release" for t in kv.trail())

    def test_trash_write_target_is_refused(self, lm):
        kv = PagedKV(lm.conf, max_slots=1, max_seq=32, block_size=8,
                     prefix_sharing=False)
        s = kv.acquire("req-a")
        with pytest.raises(RuntimeError, match="trash"):
            kv.check_writable(s)                  # no block allocated yet

    def test_pool_exhaustion_is_reported_not_silent(self, lm):
        # 2 slots x 4 blocks each but only 4 usable blocks in the pool
        kv = PagedKV(lm.conf, max_slots=2, max_seq=32, block_size=8,
                     n_blocks=5, prefix_sharing=False)
        s0, s1 = kv.acquire("a"), kv.acquire("b")
        assert kv.ensure_blocks(s0, "a", 16)      # takes 2 of 4
        assert kv.ensure_blocks(s1, "b", 16)      # takes the other 2
        assert not kv.ensure_blocks(s1, "b", 17)  # pool dry: False, loudly
        kv.release(s0)
        assert kv.ensure_blocks(s1, "b", 17)      # recovery after release

    def test_suffix_ladder_floor_follows_block_size(self):
        assert suffix_prefill_buckets(32, 4)[0] == 4
        assert suffix_prefill_buckets(32, 16)[0] == 8
        assert suffix_prefill_buckets(32, 4)[-1] == 32


# ------------------------------------------------------- engine behavior
class TestPagedEngine:
    def test_mixed_workload_zero_steady_recompiles(self, lm):
        eng = GenerationEngine.for_model(
            lm, GenerationConfig(max_slots=4, max_seq=32, block_size=4))
        try:
            run_requests(eng, REQUESTS)
            run_requests(eng, list(reversed(REQUESTS)))
            assert eng.steady_recompiles == 0
            st = eng.status()
            assert st["kv_paged"] is True
            assert st["kv"]["block_size"] == 4
            assert st["cache_bytes"] == eng.ring.cache_bytes
        finally:
            eng.shutdown()

    def test_four_times_the_slots_in_two_full_slots_of_blocks(self, lm):
        """The paged cache's capacity claim: a pool holding no more blocks
        than two full-length slots (the trash block included) serves
        eight short sequences AT ONCE, each inside one block, on the
        warmed program set."""
        dense_slots, block = 2, 4
        n_blocks = dense_slots * (32 // block)
        full = GenerationEngine.for_model(
            lm, GenerationConfig(max_slots=dense_slots, max_seq=32,
                                 block_size=block))
        eng = GenerationEngine.for_model(
            lm, GenerationConfig(max_slots=4 * dense_slots, max_seq=32,
                                 block_size=block, n_blocks=n_blocks,
                                 queue_limit=64))
        try:
            full.warmup()
            eng.warmup()
            assert eng.ring.cache_bytes < full.ring.cache_bytes
            rng = np.random.default_rng(7)
            # ticks serialize on the step lock: holding it across the
            # submits admits the whole fleet in one batch
            with eng._step_lock:
                handles = [eng.submit(
                               rng.integers(0, VOCAB, block // 2).tolist(),
                               max_new_tokens=block - block // 2)
                           for _ in range(4 * dense_slots)]
            results = [h.future.result(timeout=120) for h in handles]
            assert all(r.finish == "length" for r in results)
            assert eng.ring.peak_active == 4 * dense_slots
            assert eng.steady_recompiles == 0
        finally:
            eng.shutdown()
            full.shutdown()

    def test_retired_env_escape_hatch_is_ignored(self, lm, monkeypatch):
        """The ``DL4J_TPU_KV_PAGED=0`` hatch went with the dense ring:
        the env var does nothing and every engine builds the paged
        pool."""
        monkeypatch.setenv("DL4J_TPU_KV_PAGED", "0")
        eng = GenerationEngine.for_model(
            lm, GenerationConfig(max_slots=2, max_seq=32), start=False)
        try:
            eng.warmup()
            assert isinstance(eng.ring, PagedKV)
            assert eng.status()["kv_paged"] is True
            assert eng.status()["kv"] is not None
        finally:
            eng.shutdown()

    def test_pool_exhaustion_fails_starved_request_and_recovers(self, lm):
        """An under-provisioned pool starves a mid-decode slot: that
        request fails LOUDLY (blocks_exhausted vacate in the trail),
        already-satisfied requests finish, and the freed blocks serve
        the next request normally."""
        eng = GenerationEngine.for_model(
            lm, GenerationConfig(max_slots=2, max_seq=32, block_size=8,
                                 n_blocks=5, prefix_sharing=False))
        try:
            # 4 usable 8-token blocks: each request wants 4+14=18 tokens
            # (3 blocks) — together they exceed the pool mid-decode
            ra = eng.submit([3, 1, 4, 1], max_new_tokens=14, seed=1)
            rb = eng.submit([9, 2, 6, 5], max_new_tokens=14, seed=2)
            results, failures = [], []
            for r in (ra, rb):
                try:
                    results.append(r.future.result(timeout=120))
                except RuntimeError as e:
                    failures.append(str(e))
            assert len(failures) >= 1
            assert any("block" in f for f in failures)
            assert any(t["event"] == "vacate"
                       and t.get("reason") == "blocks_exhausted"
                       for t in eng.ring.trail())
            # engine survives and the freed pool serves a fresh request
            res = eng.generate([2, 7], max_new_tokens=4, timeout=60)
            assert res.finish == "length"
            assert eng.ring.active_slots == 0
        finally:
            eng.shutdown()

    def test_decode_exception_dump_attaches_block_events(
            self, lm, tmp_path, monkeypatch):
        """Migration honesty (ISSUE 19 satellite): the occupancy trail a
        decode-exception flight dump carries includes the paged block
        lifecycle — block_alloc at admission rides in the same trail the
        dump snapshots."""
        from deeplearning4j_tpu.observability import (FlightRecorder,
                                                      load_dump)
        from deeplearning4j_tpu.observability.recorder import \
            set_flight_recorder
        rec = FlightRecorder(directory=str(tmp_path),
                             min_dump_interval_s=0.0)
        prev = set_flight_recorder(rec)
        orig = lm._get_jitted

        def patched(kind):
            fn = orig(kind)
            if kind == "paged_decode":
                def boom(*a, **k):
                    raise RuntimeError("injected paged decode fault")
                return boom
            return fn

        monkeypatch.setattr(lm, "_get_jitted", patched)
        eng = GenerationEngine.for_model(
            lm, GenerationConfig(max_slots=2, max_seq=32, block_size=4))
        try:
            req = eng.submit([1, 2, 3], max_new_tokens=6, seed=9)
            with pytest.raises(RuntimeError, match="injected paged"):
                req.future.result(timeout=60)
            assert rec.dumps
            payload = load_dump(rec.dumps[0])
            errs = [r for r in payload["channels"]["decode"]
                    if r["type"] == "decode_error"]
            assert errs
            occ = errs[0]["occupancy"]
            assert occ.get("paged") is True
            events = [t["event"] for t in occ["trail"]]
            assert "block_alloc" in events
            assert any(t["event"] == "install" and t["request"] == req.id
                       for t in occ["trail"])
        finally:
            set_flight_recorder(prev)
            eng.shutdown()


# ---------------------------------------------------------------- int8 KV
def _int8_lm(kv_dtype=None, seed=5):
    """The TransformerLM stack hand-built so the precision policy (and
    its kv_dtype) can be attached — same topology as the module lm."""
    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.updaters import Adam
    from deeplearning4j_tpu.nn.layers.attention import (
        PositionalEncodingLayer, TransformerBlock)
    from deeplearning4j_tpu.nn.layers.feedforward import \
        EmbeddingSequenceLayer
    from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.nn.precision import PrecisionPolicy

    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater(Adam(learning_rate=3e-4)).weight_init("xavier"))
    if kv_dtype is not None:
        b = b.precision(PrecisionPolicy(kv_dtype=kv_dtype))
    lb = (b.list()
          .layer(EmbeddingSequenceLayer(n_out=16))
          .layer(PositionalEncodingLayer())
          .layer(TransformerBlock(n_heads=2, causal=True))
          .layer(TransformerBlock(n_heads=2, causal=True))
          .layer(RnnOutputLayer(n_out=VOCAB, activation="softmax",
                                loss="mcxent")))
    conf = lb.set_input_type(InputType.recurrent(VOCAB, 32)).build()
    return MultiLayerNetwork(conf).init()


class TestInt8KV:
    def test_int8_kv_halves_cache_bytes_with_greedy_parity(self):
        """``PrecisionPolicy.kv_dtype='int8'``: K/V pools store one byte
        per element (+ f32 per-token/per-head scales) — under half the
        f32 pool bytes at head_dim 8 — and greedy streams match the f32
        cache within tolerance (identical params; only cache storage
        differs)."""
        f32 = _int8_lm(kv_dtype=None)
        i8 = _int8_lm(kv_dtype="int8")
        # identical init: the policy changes storage, not parameters
        import jax
        for a, b in zip(jax.tree_util.tree_leaves(f32.params),
                        jax.tree_util.tree_leaves(i8.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [5, 3, 5, 8]]
        cfg = dict(max_slots=2, max_seq=32, block_size=4)
        e32 = GenerationEngine.for_model(f32, GenerationConfig(**cfg))
        try:
            want = [e32.generate(p, max_new_tokens=8, timeout=60).tokens
                    for p in prompts]
            f32_bytes = e32.ring.cache_bytes
        finally:
            e32.shutdown()
        e8 = GenerationEngine.for_model(i8, GenerationConfig(**cfg))
        try:
            got = [e8.generate(p, max_new_tokens=8, timeout=60).tokens
                   for p in prompts]
            i8_bytes = e8.ring.cache_bytes
            assert e8.ring.kv_dtype == "int8"
            assert e8.status()["kv"]["kv_dtype"] == "int8"
        finally:
            e8.shutdown()
        assert i8_bytes <= 0.5 * f32_bytes
        # greedy-parity-within-tolerance: argmax is robust to the <=1%
        # relative quantization error at these magnitudes; a rare tied
        # logit may flip one tail token, never the stream wholesale
        same = sum(int(g == w) for g, w in zip(got, want))
        assert same >= len(prompts) - 1, (got, want)
