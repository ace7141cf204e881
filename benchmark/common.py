"""What every part of the benchmark shares: printing, files found by name,
the peaks table, the seed, where a run's files go and the device line.

Nothing here imports the program, and nothing here touches JAX while it is
imported: the tests load it on machines without a chip.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(ROOT)
OUT = os.path.join(ROOT, "out")
# fixed, inside the checkout: the path is part of the cache's key
COMPILE_CACHE = os.path.join(ROOT, "out", "compile_cache")


def say(*parts) -> None:
    """Progress, on standard error: standard output carries the result."""
    print(*parts, file=sys.stderr, flush=True)


def load_json(*rel):
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


def load_manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by the name the data gives."""
    if not name.replace("_", "").replace(".", "").replace("-", "").isalnum():
        raise ValueError(f"not a module name: {name!r}")
    path = os.path.join(ROOT, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module for '{name}': {path}")
    module_name = f"benchmark.{kind}.{name.replace('.', '_dot_')}"
    if module_name in sys.modules:
        return sys.modules[module_name]
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = module
    spec.loader.exec_module(module)
    return module


def peaks_for(device_kind: str) -> dict:
    table = load_json("peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind '{device_kind}' is not in "
                       "benchmark/peaks.json; an unknown chip has no peak")
    return table[device_kind]


def keep_writes_inside() -> str:
    """Keep what a run writes inside the checkout: point JAX's persistent
    cache at the fixed directory, unless the machine names one itself (the
    program sets no directory where the variable is set), and the TPU
    library's logs, which otherwise go to ``/tmp/tpu_logs``, beside it.
    Called before JAX or the program is imported."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE
        os.makedirs(path, exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    if not os.environ.get("TPU_LOG_DIR"):
        logs = os.path.join(OUT, "tpu_logs")
        os.makedirs(logs, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = logs
    return path


def seed_key(seed: int, stream: int = 0):
    """A JAX key from any whole-number seed (the driver's pass 2**31)."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def device_line(devices, memory_peak_bytes=None) -> dict:
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if memory_peak_bytes is not None:
        out["memory_peak_bytes"] = int(memory_peak_bytes)
    return out


def memory_peak_bytes(devices) -> int:
    """The fullest chip's peak, as the runtime counts it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
