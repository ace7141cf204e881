"""Model zoo (reference ``deeplearning4j-zoo``) + bench/flagship selection."""
import numpy as np

from .zoo import (ALL_MODELS, AlexNet, EvaByteLM, FaceNetNN4Small2, GoogLeNet,
                  InceptionResNetV1, JoyAIFlashLM, LeNet, OuroLM, ResNet50,
                  SimpleCNN, ModelSelector, TextGenerationLSTM, TransformerLM, TrinityLM,
                  VGG16, VGG19, ZooModel)

__all__ = [
    "ALL_MODELS", "AlexNet", "EvaByteLM", "FaceNetNN4Small2", "GoogLeNet",
    "InceptionResNetV1", "JoyAIFlashLM", "LeNet", "OuroLM", "ResNet50",
    "SimpleCNN",
    "ModelSelector", "TextGenerationLSTM", "TransformerLM", "TrinityLM",
    "VGG16", "VGG19", "ZooModel",
    "available_bench_model", "flagship_entry_model", "generate_tokens",
]


def available_bench_model(batch: int = 32, image: int = 224,
                          compute_dtype: str = "bfloat16"):
    """Flagship bench model: ResNet50-ImageNet (the BASELINE.md north-star
    metric is ResNet50 examples/sec/chip).  bf16 compute is the TPU-native
    default (f32 master params); DL4J_TPU_BENCH_DTYPE=float32 disables.
    Returns (model, (x, y))."""
    import os
    compute_dtype = os.environ.get("DL4J_TPU_BENCH_DTYPE", compute_dtype)
    model = ResNet50(num_classes=1000,
                     compute_dtype=None if compute_dtype == "float32"
                     else compute_dtype,
                     input_shape=(image, image, 3)).init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, image, image, 3), dtype=np.float32)
    y = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)]
    return model, (x, y)


def flagship_entry_model():
    """Small-shape flagship instance for the driver's single-chip compile
    check (same architecture, quick compile)."""
    model = ResNet50(num_classes=100, input_shape=(96, 96, 3)).init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 96, 96, 3), dtype=np.float32)
    y = np.eye(100, dtype=np.float32)[rng.integers(0, 100, 8)]
    return model, (x, y)


def generate_tokens(net, prompt_ids, n_tokens: int, temperature: float = 1.0,
                    seed: int = 0):
    """Autoregressive sampling through the KV-cached ``rnn_time_step``
    stream (works for TransformerLM and recurrent LMs alike).
    prompt_ids: [batch, t0] ints.  Returns [batch, t0 + n_tokens]."""
    rng = np.random.default_rng(seed)
    prompt_ids = np.asarray(prompt_ids)
    caches = [c for c in (getattr(l, "max_cache_len", None)
                          for l in net.layers) if c]
    total = prompt_ids.shape[1] + n_tokens
    if caches and total > min(caches):
        raise ValueError(
            f"prompt + n_tokens = {total} exceeds the smallest KV cache "
            f"({min(caches)}); raise max_cache_len on the attention layers")
    net.rnn_clear_previous_state()
    probs = np.asarray(net.rnn_time_step(prompt_ids))[:, -1]   # [b, v]
    out = [prompt_ids]
    for _ in range(n_tokens):
        if temperature <= 0:
            nxt = probs.argmax(-1)
        else:
            logits = np.log(np.maximum(probs, 1e-9)) / temperature
            p = np.exp(logits - logits.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            nxt = np.array([rng.choice(p.shape[-1], p=row) for row in p])
        nxt = nxt.astype(prompt_ids.dtype)[:, None]
        out.append(nxt)
        probs = np.asarray(net.rnn_time_step(nxt))[:, -1]
    return np.concatenate(out, axis=1)
