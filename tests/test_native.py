"""Native C++ kernel tests: build, bind, and verify numerics against the
pure-Python fallbacks and the jitted device codecs (reference test model:
the cuDNN-vs-builtin validation pattern, ``ValidateCudnnLSTM``-style)."""
import numpy as np
import pytest

from deeplearning4j_tpu.utils import native
from deeplearning4j_tpu.utils.native import (available, bitmap_decode_native,
                                             bitmap_encode_native,
                                             decode_cifar, parse_csv,
                                             threshold_decode_native,
                                             threshold_encode_native,
                                             u8_to_f32)


def test_native_library_builds():
    # the toolchain is part of this environment: the native path must be live
    assert available(), "g++ build of native/dl4j_tpu_native.cpp failed"


def test_loaded_library_is_named_by_the_source_it_was_built_from():
    """A stale build left in the git-ignored directory is never trusted:
    the library's name carries the digest of native_src.cpp as on disk."""
    import hashlib
    digest = hashlib.sha256(native._SRC.read_bytes()).hexdigest()[:16]
    assert native.library_path() == str(
        native._BUILD_DIR / f"libdl4j_tpu_native-{digest}.so")


class TestThresholdCodec:
    def test_roundtrip_reconstructs(self):
        rng = np.random.default_rng(0)
        g = rng.standard_normal(2048).astype(np.float32) * 0.01
        g[[5, 99, 1000]] = [0.5, -0.8, 0.3]
        idx, signs, residual = threshold_encode_native(g, 0.1)
        assert set(idx) == {5, 99, 1000}
        dec = threshold_decode_native(idx, signs, 0.1, g.size)
        np.testing.assert_allclose(dec + residual, g, atol=1e-6)

    def test_topk_cap(self):
        g = np.zeros(64, np.float32)
        g[:6] = [1, -2, 3, -4, 5, -6]
        idx, signs, residual = threshold_encode_native(g, 0.5, max_k=3)
        assert set(idx) == {3, 4, 5}
        assert list(signs) == [-1, 1, -1]
        dec = threshold_decode_native(idx, signs, 0.5, 64)
        np.testing.assert_allclose(dec + residual, g, atol=1e-6)

    def test_matches_jitted_device_codec(self):
        from deeplearning4j_tpu.parallel.accumulation import (
            threshold_decode, threshold_encode)
        rng = np.random.default_rng(1)
        g = rng.standard_normal(512).astype(np.float32)
        msg, res_dev = threshold_encode(g, 0.7)
        idx, signs, res_nat = threshold_encode_native(g, 0.7)
        assert set(msg["idx"]) == set(idx)
        np.testing.assert_allclose(np.asarray(res_dev), res_nat, atol=1e-6)

    def test_matches_python_fallback(self, monkeypatch):
        rng = np.random.default_rng(2)
        g = rng.standard_normal(300).astype(np.float32)
        idx_n, signs_n, res_n = threshold_encode_native(g, 0.5)
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", True)
        idx_p, signs_p, res_p = threshold_encode_native(g, 0.5)
        np.testing.assert_array_equal(idx_n, idx_p)
        np.testing.assert_array_equal(signs_n, signs_p)
        np.testing.assert_allclose(res_n, res_p, atol=1e-6)


class TestBitmapCodec:
    def test_roundtrip(self):
        rng = np.random.default_rng(3)
        g = rng.standard_normal(1001).astype(np.float32)
        packed, residual = bitmap_encode_native(g, 0.5)
        assert packed.nbytes == (1001 + 3) // 4
        dec = bitmap_decode_native(packed, 0.5, 1001)
        np.testing.assert_allclose(dec + residual, g, atol=1e-6)

    def test_matches_python_fallback(self, monkeypatch):
        rng = np.random.default_rng(4)
        g = rng.standard_normal(257).astype(np.float32)
        p_n, r_n = bitmap_encode_native(g, 0.3)
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", True)
        p_p, r_p = bitmap_encode_native(g, 0.3)
        np.testing.assert_array_equal(p_n, p_p)
        np.testing.assert_allclose(r_n, r_p, atol=1e-6)


class TestDecode:
    def test_u8_scale(self):
        data = np.arange(256, dtype=np.uint8)
        out = u8_to_f32(data)
        np.testing.assert_allclose(out, data / 255.0, rtol=1e-6)

    def test_cifar_decode_matches_numpy(self):
        rng = np.random.default_rng(5)
        n = 7
        rec = np.empty((n, 3073), np.uint8)
        rec[:, 0] = rng.integers(0, 10, n)
        rec[:, 1:] = rng.integers(0, 256, (n, 3072))
        labels, images = decode_cifar(rec.tobytes())
        assert images.shape == (n, 32, 32, 3)
        np.testing.assert_array_equal(labels, rec[:, 0])
        chw = rec[:, 1:].reshape(n, 3, 32, 32)
        np.testing.assert_allclose(
            images, chw.transpose(0, 2, 3, 1) / 255.0, rtol=1e-6)

    def test_cifar_bad_length(self):
        with pytest.raises(ValueError, match="3073"):
            decode_cifar(b"\x00" * 100)


class TestCsvParse:
    def test_parse_basic(self):
        out = parse_csv(b"1.5,2.5\n3.0,4.0\n")
        np.testing.assert_allclose(out, [[1.5, 2.5], [3.0, 4.0]])

    def test_parse_no_trailing_newline_and_crlf(self):
        out = parse_csv(b"1,2\r\n3,4")
        np.testing.assert_allclose(out, [[1, 2], [3, 4]])

    def test_parse_scientific_and_negative(self):
        out = parse_csv(b"-1e-3,2.5e2\n0.0,-4\n")
        np.testing.assert_allclose(out, [[-0.001, 250.0], [0.0, -4.0]])

    def test_ragged_raises(self):
        with pytest.raises(ValueError):
            parse_csv(b"1,2\n3\n")

    def test_strictness_matches_fallback(self, monkeypatch):
        # both paths must accept/reject the SAME inputs
        cases = [b"1,,3\n", b"1 2\n3 4\n", b"1, \n2,3\n", b"a,b\n",
                 b"1, 2\n 3 ,4\n", b""]
        native_results = []
        for c in cases:
            try:
                native_results.append(parse_csv(c).tolist())
            except ValueError:
                native_results.append("raise")
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", True)
        for c, expect in zip(cases, native_results):
            try:
                got = parse_csv(c).tolist()
            except ValueError:
                got = "raise"
            assert got == expect, (c, got, expect)

    def test_matches_python_fallback(self, monkeypatch):
        text = b"1.25,2\n-3,4.75\n"
        a = parse_csv(text)
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", True)
        b = parse_csv(text)
        np.testing.assert_array_equal(a, b)


class TestHostEncodingHandler:
    def test_host_backend_matches_device(self):
        from deeplearning4j_tpu.parallel.accumulation import EncodingHandler
        rng = np.random.default_rng(6)
        g = rng.standard_normal(1024).astype(np.float32) * 0.05
        dev = EncodingHandler(initial_threshold=0.02, decay=1.0, boost=1.0)
        host = EncodingHandler(initial_threshold=0.02, decay=1.0, boost=1.0,
                               backend="host")
        m1, m2 = dev.encode_update(g), host.encode_update(g)
        assert m1["kind"] == m2["kind"]
        if m1["kind"] == "threshold":
            assert set(m1["idx"]) == set(m2["idx"])
        np.testing.assert_allclose(np.asarray(dev.residual),
                                   np.asarray(host.residual), atol=1e-6)

    def test_bad_backend(self):
        from deeplearning4j_tpu.parallel.accumulation import EncodingHandler
        with pytest.raises(ValueError, match="backend"):
            EncodingHandler(backend="gpu")


class TestCorpusIndexer:
    """dl4j_index_corpus — the DataVec/libnd4j data-loader role: tokenize +
    vocab-index natively with EXACT str.split semantics (the bulk-emission
    oracle in test_nlp additionally pins end-to-end training equivalence)."""

    VOCAB = {"the": 0, "quick": 1, "brown": 2, "fox": 3, "jumps": 4,
             "over": 5, "lazy": 6, "dog": 7}

    def test_matches_str_split_semantics(self):
        from deeplearning4j_tpu.utils import native
        if not native.available():
            pytest.skip("no native toolchain")
        sentences = ["the quick brown fox", "jumps over  the lazy dog",
                     "", "   ", "oov words here the", "\tthe\nquick\r"]
        arrs = native.index_corpus(sentences, self.VOCAB)
        assert arrs is not None
        g = self.VOCAB.get
        for a, s in zip(arrs, sentences):
            expect = [g(t) for t in s.split() if g(t) is not None]
            assert a.tolist() == expect, (s, a.tolist(), expect)

    def test_unicode_whitespace_bails_to_python(self):
        from deeplearning4j_tpu.utils import native
        if not native.available():
            pytest.skip("no native toolchain")
        # ideographic space U+3000 and NBSP are str.split separators the
        # native path must refuse rather than mis-tokenize
        assert native.index_corpus(["a　b"], self.VOCAB) is None
        assert native.index_corpus(["a b"], self.VOCAB) is None
        # ordinary multibyte text without unicode spaces is fine
        arrs = native.index_corpus(["the 快 fox"], self.VOCAB)
        assert arrs is not None and arrs[0].tolist() == [0, 3]

    def test_word2vec_training_identical_across_paths(self, monkeypatch):
        from deeplearning4j_tpu.nlp.word2vec import Word2Vec
        from deeplearning4j_tpu.nlp import sequence_vectors as SV
        from deeplearning4j_tpu.utils import native
        if not native.available():
            pytest.skip("no native toolchain")
        sents = ["the quick brown fox jumps", "over the lazy dog the fox"] * 30

        def fit(native_on):
            w = Word2Vec(sentences=sents, layer_size=16, window=3,
                         negative=3, epochs=2, seed=5, min_word_frequency=1)
            if not native_on:
                monkeypatch.setattr(type(w), "_raw_sentences",
                                    lambda self: None)
            w.fit()
            monkeypatch.undo()
            return np.asarray(w.lookup_table.syn0)

        used = []
        orig = SV.SequenceVectors._try_native_index

        def spy(self, index_map):
            out = orig(self, index_map)
            used.append(out is not None)
            return out

        monkeypatch.setattr(SV.SequenceVectors, "_try_native_index", spy)
        a = fit(True)
        assert used and used[0], "native path was not taken"
        b = fit(False)
        np.testing.assert_array_equal(a, b)
