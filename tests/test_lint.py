"""graftlint: per-rule positive/negative fixtures + the tier-1 gate that
keeps ``deeplearning4j_tpu/`` clean modulo the checked-in baseline.

Every rule JX001–JX031 has at least one fixture that MUST fire and one
that MUST stay silent; the whole-program concurrency pass (JX018–JX021)
additionally unit-tests its thread-entry / guarded-by / lock-order
inference layers.  The gate test makes every future PR re-lint the whole
package without separate CI wiring, and the wall-time budget test keeps
the full run inside the developer loop.
"""
import ast
import json
import os
import re
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from tools.graftlint import (Baseline, PROGRAM_RULES,  # noqa: E402
                             RULE_DOCS, RULES, lint_paths, lint_source)

PKG = REPO_ROOT / "deeplearning4j_tpu"
BASELINE = REPO_ROOT / "tools" / "graftlint" / "baseline.json"


def rules_of(src: str):
    return {f.rule for f in lint_source(textwrap.dedent(src), "fix.py")}


def findings(src: str, select=None):
    return lint_source(textwrap.dedent(src), "fix.py", select=select)


# ---------------------------------------------------------------- JX001
def test_jx001_positive_numpy_on_traced_value():
    assert "JX001" in rules_of("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.log(x)
    """)


def test_jx001_positive_jit_call_form():
    assert "JX001" in rules_of("""
        import jax
        import numpy as np

        def f(x):
            return np.tanh(x * 2)

        g = jax.jit(f)
    """)


def test_jx001_negative_host_constant_and_unjitted():
    assert "JX001" not in rules_of("""
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def f(x):
            scale = np.log(2.0)      # host constant: runs once at trace
            return jnp.log(x) * scale

        def g(x):
            return np.log(x)         # not a jit scope
    """)


# ---------------------------------------------------------------- JX002
def test_jx002_positive_if_on_tracer():
    assert "JX002" in rules_of("""
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """)


def test_jx002_positive_while_on_derived_value():
    assert "JX002" in rules_of("""
        import jax

        @jax.jit
        def f(x):
            y = x * 2
            while y < 10:
                y = y + 1
            return y
    """)


def test_jx002_negative_static_arg_and_shape():
    assert "JX002" not in rules_of("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("mode",))
        def f(x, mode):
            if mode == "fast":          # static arg: concrete at trace
                return x
            if x.shape[0] > 1:          # shape is trace-static
                return x + 1
            if len(x) > 2:              # len() is static too
                return x + 2
            return x
    """)


def test_jx002_negative_static_argnums_positional():
    assert "JX002" not in rules_of("""
        import jax

        def f(x, k):
            if k > 2:
                return x * k
            return x

        g = jax.jit(f, static_argnums=(1,))
    """)


# ---------------------------------------------------------------- JX003
def test_jx003_positive_float_and_item_in_fit_loop():
    got = findings("""
        import jax

        def fit(model, batches, step):
            for b in batches:
                loss = step(b)
                model.score = float(loss)
                model.last = loss.item()
    """, select=["JX003"])
    assert len(got) == 2


def test_jx003_negative_shape_reads_and_after_loop():
    assert "JX003" not in rules_of("""
        import jax
        import numpy as np

        def fit(model, batches, step):
            loss = None
            for b in batches:
                n = int(b.shape[0])            # static metadata
                m = int(getattr(b, "shape", (0,))[0])
                idx = np.array([i for i in range(n)])  # host ETL
                loss = step(b)
            model.score = float(loss)          # one sync after the loop
    """)


def test_jx003_negative_not_a_training_function():
    assert "JX003" not in rules_of("""
        import jax

        def report(values):
            out = []
            for v in values:
                out.append(float(v))
            return out
    """)


def test_jx003_negative_module_without_jax():
    assert "JX003" not in rules_of("""
        def fit(model, batches):
            for b in batches:
                model.score = float(b)
    """)


# ---------------------------------------------------------------- JX004
def test_jx004_positive_jit_in_loop():
    assert "JX004" in rules_of("""
        import jax

        def run(fs, x):
            outs = []
            for f in fs:
                outs.append(jax.jit(f)(x))
            return outs
    """)


def test_jx004_positive_immediate_invocation():
    assert "JX004" in rules_of("""
        import jax

        def once(f, x):
            return jax.jit(f)(x)
    """)


def test_jx004_negative_hoisted_jit():
    assert "JX004" not in rules_of("""
        import jax

        def make_step(f):
            step = jax.jit(f)
            def run(xs):
                return [step(x) for x in xs]
            return run
    """)


# ---------------------------------------------------------------- JX005
def test_jx005_positive_list_static_argnums():
    assert "JX005" in rules_of("""
        import jax

        def f(x, k):
            return x * k

        g = jax.jit(f, static_argnums=[1])
    """)


def test_jx005_negative_tuple_static_argnums():
    assert "JX005" not in rules_of("""
        import jax

        def f(x, k):
            return x * k

        g = jax.jit(f, static_argnums=(1,))
        h = jax.jit(f, static_argnames=("k",))
    """)


# ---------------------------------------------------------------- JX006
def test_jx006_positive_self_mutation():
    assert "JX006" in rules_of("""
        import jax

        class Model:
            @jax.jit
            def step(self, x):
                self.calls = self.calls + 1
                return x * 2
    """)


def test_jx006_positive_global_mutation():
    assert "JX006" in rules_of("""
        import jax

        COUNT = 0

        @jax.jit
        def f(x):
            global COUNT
            COUNT += 1
            return x
    """)


def test_jx006_negative_local_state_and_unjitted():
    assert "JX006" not in rules_of("""
        import jax

        class Model:
            @jax.jit
            def step(self, x):
                y = x * 2          # locals are fine
                return y

            def host_update(self):
                self.calls = 1     # not traced: fine
    """)


# ---------------------------------------------------------------- JX007
def test_jx007_positive_bare_except():
    assert "JX007" in rules_of("""
        def f():
            try:
                return 1
            except:
                return 2
    """)


def test_jx007_negative_typed_except():
    assert "JX007" not in rules_of("""
        def f():
            try:
                return 1
            except Exception:
                return 2
            except (ValueError, OSError):
                return 3
    """)


# ---------------------------------------------------------------- JX008
def test_jx008_positive_mutable_defaults():
    got = findings("""
        def f(a, xs=[], m={}):
            return a

        def g(b, s=set()):
            return b
    """, select=["JX008"])
    assert len(got) == 3


def test_jx008_negative_none_and_immutable_defaults():
    assert "JX008" not in rules_of("""
        def f(a, xs=None, t=(), name="x", n=3):
            xs = [] if xs is None else xs
            return a
    """)


# ---------------------------------------------------------------- JX009
def test_jx009_positive_unsynced_timing():
    assert "JX009" in rules_of("""
        import time
        import jax.numpy as jnp

        def bench(f, x):
            t0 = time.perf_counter()
            y = f(x) + jnp.ones(3)
            return time.perf_counter() - t0
    """)


def test_jx009_negative_block_until_ready():
    assert "JX009" not in rules_of("""
        import time
        import jax
        import jax.numpy as jnp

        def bench(f, x):
            t0 = time.perf_counter()
            y = f(x) + jnp.ones(3)
            jax.block_until_ready(y)
            return time.perf_counter() - t0
    """)


def test_jx009_negative_fetch_closed_and_deadlines():
    assert "JX009" not in rules_of("""
        import time
        import numpy as np
        import jax.numpy as jnp

        def bench(f, x):
            t0 = time.perf_counter()
            y = float(np.asarray(f(x))[0])   # fetch closes the async gap
            return time.perf_counter() - t0

        def poll(q, timeout):
            deadline = time.time() + timeout   # deadline, not measurement
            while time.time() < deadline:
                v = q.get()
                if v is not None:
                    return v * jnp.ones(1)
    """)


# ---------------------------------------------------------------- JX010
def test_jx010_positive_float64_astype():
    assert "JX010" in rules_of("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return x.astype(jnp.float64)
    """)


def test_jx010_positive_dtype_string():
    assert "JX010" in rules_of("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return jnp.zeros_like(x, dtype="float64")
    """)


def test_jx010_negative_float32_and_outside_jit():
    assert "JX010" not in rules_of("""
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def f(x):
            return x.astype(jnp.float32)

        def host(x):
            return np.float64(x)   # host-side double is fine
    """)


# ---------------------------------------------------------------- JX011
def test_jx011_positive_interval_subtraction():
    assert "JX011" in rules_of("""
        import time

        def measure(f):
            t0 = time.time()
            f()
            return time.time() - t0
    """)


def test_jx011_positive_propagated_sample_and_bare_import():
    # one-hop propagation (now -> self._last) across methods, with
    # `from time import time`
    assert "JX011" in rules_of("""
        from time import time

        class Listener:
            def start(self):
                now = time()
                self._last = now

            def rate(self, n):
                now = time()
                return n / (now - self._last)
    """)


def test_jx011_negative_deadline_idiom_and_timestamps():
    assert "JX011" not in rules_of("""
        import time

        def wait(poll, timeout):
            deadline = time.time() + timeout
            while time.time() < deadline:
                remaining = deadline - time.time()  # remaining, not elapsed
                poll(remaining)

        def stamp(record):
            record["ts"] = time.time()   # timestamp: no arithmetic
            return record
    """)


def test_jx011_negative_perf_counter_interval():
    assert "JX011" not in rules_of("""
        import time

        def measure(f):
            t0 = time.perf_counter()
            f()
            return time.perf_counter() - t0
    """)


# ---------------------------------------------------------------- JX012
def test_jx012_positive_device_put_in_loop():
    assert "JX012" in rules_of("""
        import jax

        def feed(step, batches):
            for b in batches:
                step(jax.device_put(b))
    """)


def test_jx012_positive_bare_device_put_in_while():
    assert "JX012" in rules_of("""
        from jax import device_put

        def feed(step, batches):
            while batches:
                step(device_put(batches.pop()))
    """)


def test_jx012_positive_asarray_of_device_value_in_loop():
    assert "JX012" in rules_of("""
        import jax.numpy as jnp
        import numpy as np

        def collect(xs):
            d = jnp.asarray(xs)
            out = []
            for i in range(10):
                out.append(np.asarray(d))   # D2H fetch every iteration
            return out
    """)


def test_jx012_negative_hoisted_and_host_and_jit():
    assert "JX012" not in rules_of("""
        import jax
        import jax.numpy as jnp
        import numpy as np

        def place(b):
            return jax.device_put(b)        # no loop: a prefetch stage

        def loop(items):
            total = 0.0
            for it in items:
                a = np.asarray(it)          # host list -> host array
                total += a.sum()
            return total

        @jax.jit
        def f(x):
            for i in range(3):              # unrolled at trace time
                x = jax.device_put(x)
            return x
    """)


def test_jx012_pragma_suppresses():
    assert "JX012" not in rules_of("""
        import jax

        def prefetch(batches):
            for b in batches:
                yield jax.device_put(b)  # graftlint: disable=JX012  (the prefetch stage itself)
    """)


# ---------------------------------------------------------------- JX013
def test_jx013_positive_method_local_jit_closes_over_self():
    assert "JX013" in rules_of("""
        import jax

        class Net:
            def make_step(self):
                def step(params, x):
                    return params * self.scale + x
                return jax.jit(step)
    """)


def test_jx013_positive_decorated_def_inside_method():
    assert "JX013" in rules_of("""
        import jax

        class Net:
            def fit(self, x):
                @jax.jit
                def step(p):
                    return self.forward(p, x)
                return step(self.params)
    """)


def test_jx013_positive_lambda_argument():
    assert "JX013" in rules_of("""
        import jax

        class Net:
            def make(self):
                return jax.jit(lambda x: x * self.scale)
    """)


def test_jx013_negative_self_free_closure_and_module_level():
    assert "JX013" not in rules_of("""
        import jax

        def build_step(conf, tx):
            def step(params, x):
                return params * conf.scale + tx(x)
            return jax.jit(step)

        class Net:
            def make_step(self):
                conf = self.conf
                def step(params, x):       # closes over conf, NOT self
                    return params * conf.scale + x
                return jax.jit(step)
    """)


def test_jx013_negative_jit_outside_methods():
    assert "JX013" not in rules_of("""
        import jax

        def helper(f):
            def step(x):
                return f(x)
            return jax.jit(step)
    """)


# ---------------------------------------------------------------- JX014
def test_jx014_positive_zipfile_write_to_checkpoint_path():
    assert "JX014" in rules_of("""
        import os
        import zipfile

        def save(d, tag, payload):
            path = os.path.join(d, f"checkpoint_{tag}.zip")
            with zipfile.ZipFile(path, "w") as zf:
                zf.writestr("a", payload)
    """)


def test_jx014_positive_open_wb_on_ckpt_name_and_savez_model_zip():
    got = rules_of("""
        import numpy as np

        def save(d, data, arrs, ckpt_file):
            with open(ckpt_file, "wb") as f:
                f.write(data)
            np.savez(d + "/bestModel.zip", **arrs)
    """)
    assert "JX014" in got


def test_jx014_positive_one_hop_alias():
    assert "JX014" in rules_of("""
        import os

        def save(d, data):
            path = os.path.join(d, "ckpt-00000001.bin")
            dst = path
            with open(dst, "wb") as f:
                f.write(data)
    """)


def test_jx014_negative_atomic_helper_reads_and_plain_paths():
    assert "JX014" not in rules_of("""
        import io
        import zipfile
        import numpy as np
        from deeplearning4j_tpu.faulttolerance.atomic import atomic_file

        def save(dst, arrs, log_path, ckpt_path, shard_path, data):
            with atomic_file(dst) as tmp:          # helper: tmp is runtime
                with zipfile.ZipFile(tmp, "w") as zf:
                    zf.writestr("a", b"x")
            buf = io.BytesIO()
            np.savez(buf, **arrs)                  # in-memory buffer
            with open(log_path, "wb") as f:        # not checkpoint-like
                f.write(data)
            with zipfile.ZipFile(ckpt_path, "r") as zf:    # read-only
                zf.namelist()
            np.savez(shard_path, **arrs)           # not checkpoint-like
            with open(ckpt_path, "w") as f:        # text mode: manifest
                f.write("{}")                      # writers go via helper,
                                                   # but rule targets "wb"
    """)


def test_jx014_negative_same_name_in_unrelated_function():
    # name taint is per-scope: `path` holding a checkpoint name in one
    # function must not flag an unrelated `path` written elsewhere
    assert "JX014" not in rules_of("""
        import os

        def a(d):
            path = os.path.join(d, "checkpoint.zip")
            return path

        def b(d, data):
            path = os.path.join(d, "stats.bin")
            with open(path, "wb") as f:
                f.write(data)
    """)


# ---------------------------------------------------------------- JX015
def test_jx015_positive_astype_on_device_value_in_loop():
    assert "JX015" in rules_of("""
        import jax.numpy as jnp

        def train(step, batches, params):
            xb = jnp.zeros((4, 4))
            for b in batches:
                xb = xb.astype(jnp.bfloat16)    # cast dispatch per step
                params = step(params, xb)
            return params
    """)


def test_jx015_positive_dtype_ctor_in_loop():
    assert "JX015" in rules_of("""
        import jax.numpy as jnp

        def train(step, params, lr):
            for i in range(100):
                params = step(params, jnp.float32(lr))
            return params
    """)


def test_jx015_negative_host_numpy_hoisted_and_jit():
    assert "JX015" not in rules_of("""
        import jax
        import jax.numpy as jnp
        import numpy as np

        def etl(batches):
            out = []
            for b in batches:
                out.append(b.astype(np.float32))   # host ETL: legal
            return out

        def train(step, params, batches, lr):
            lr_s = jnp.float32(lr)                 # hoisted: placed once
            for b in batches:
                params = step(params, b, lr_s, np.float32(0.1))
            return params

        @jax.jit
        def f(x):
            for i in range(3):
                x = x.astype(jnp.bfloat16)         # traced, not dispatched
            return x
    """)


def test_jx015_pragma_suppresses():
    assert "JX015" not in rules_of("""
        import jax.numpy as jnp

        def probe(step, params):
            for i in range(3):
                step(params, jnp.float32(i))  # graftlint: disable=JX015  (3-iteration probe)
    """)


# ---------------------------------------------------------------- JX016
def test_jx016_positive_unbounded_reconnect_loop():
    assert "JX016" in rules_of("""
        import socket

        def keep_publishing(host, port, frames):
            while True:
                try:
                    sock = socket.create_connection((host, port))
                    for f in frames:
                        sock.sendall(f)
                    return
                except OSError:
                    continue          # hammers a dead hub forever
    """)


def test_jx016_positive_retry_reaches_nested_try():
    assert "JX016" in rules_of("""
        def poll_forever(fetch):
            while True:
                try:
                    return fetch()
                except ConnectionError:
                    fetch = fetch
                    continue
    """)


def test_jx016_negative_backoff_and_budget():
    assert "JX016" not in rules_of("""
        import time

        def with_backoff(connect, policy):
            while True:
                try:
                    return connect()
                except OSError:
                    policy.sleep(1)       # budgeted backoff: legal
                    continue

        def with_budget(connect):
            attempt = 0
            while True:
                try:
                    return connect()
                except OSError:
                    attempt += 1
                    if attempt > 3:
                        raise
                    continue

        def bounded_for(connect, policy):
            for attempt in range(3):      # bounded loop, not while True
                try:
                    return connect()
                except OSError:
                    continue
    """)


def test_jx016_negative_queue_drain_and_inner_loop():
    assert "JX016" not in rules_of("""
        import queue

        def drain(q):
            while True:
                try:
                    item = q.get_nowait()   # break, not continue: a drain
                except queue.Empty:
                    break
                yield item

        def outer(jobs, run):
            while True:
                for j in jobs:
                    try:
                        run(j)
                    except RuntimeError:
                        continue         # binds to the inner for loop
                return
    """)


def test_jx016_pragma_suppresses():
    assert "JX016" not in rules_of("""
        def spin(connect):
            while True:
                try:
                    return connect()
                except OSError:  # graftlint: disable=JX016  (probe rig)
                    continue
    """)


# ---------------------------------------------------------------- JX017
_SERVING_PATH = "deeplearning4j_tpu/serving/fix.py"


def rules_at(src: str, path: str):
    return {f.rule for f in lint_source(textwrap.dedent(src), path)}


def test_jx017_positive_unbounded_queues_in_serving_scope():
    src = """
        import queue
        import multiprocessing as mp
        from queue import Queue

        def build():
            a = queue.Queue()          # unbounded
            b = mp.Queue()             # unbounded
            c = Queue()                # unbounded (from-import)
            return a, b, c
    """
    findings = lint_source(textwrap.dedent(src), _SERVING_PATH)
    assert sum(f.rule == "JX017" for f in findings) == 3


def test_jx017_positive_streaming_and_parallel_scope():
    src = """
        import queue

        q = queue.PriorityQueue()
    """
    for path in ("deeplearning4j_tpu/streaming/fix.py",
                 "deeplearning4j_tpu/parallel/fix.py"):
        assert "JX017" in rules_at(src, path)


def test_jx017_negative_bounded_or_deliberate():
    assert "JX017" not in rules_at("""
        import queue
        import multiprocessing as mp

        def build(limit):
            a = queue.Queue(maxsize=limit)    # keyword bound
            b = queue.Queue(256)              # positional bound
            c = mp.Queue(maxsize=0)           # deliberate unboundedness
            return a, b, c
    """, _SERVING_PATH)


def test_jx017_negative_out_of_scope_module():
    # ETL/data modules size queues to their prefetch depth — out of scope
    assert "JX017" not in rules_at("""
        import queue

        q = queue.Queue()
    """, "deeplearning4j_tpu/data/fix.py")
    assert "JX017" not in rules_of("""
        import queue

        q = queue.Queue()
    """)


def test_jx017_pragma_suppresses():
    assert "JX017" not in rules_at("""
        import queue

        q = queue.Queue()  # graftlint: disable=JX017  (drained every tick)
    """, _SERVING_PATH)


# ---------------------------------------------------------------- JX022
def test_jx022_positive_registry_lookup_in_loop():
    src = """
        def consume(messages, reg):
            for m in messages:
                reg.counter("broker_messages_total", "doc").inc()

        def poll(reg):
            while True:
                reg.gauge("queue_depth", "doc").set(1)
    """
    fs = findings(src)
    assert sum(f.rule == "JX022" for f in fs) == 2


def test_jx022_positive_constant_labels_in_loop():
    assert "JX022" in rules_of("""
        def run(batches, etl_h):
            for b in batches:
                etl_h.labels("fetch").observe(0.1)
    """)


def test_jx022_negative_cached_child_and_varying_labels():
    assert "JX022" not in rules_of("""
        def run(batches, reg):
            c = reg.counter("training_steps_total", "doc")
            age = reg.gauge("hb_age", "doc", ("worker",))
            for i, b in enumerate(batches):
                c.inc()
                age.labels(str(i)).set(1.0)   # varying label: legal
    """)


def test_jx022_negative_lookup_outside_loop_and_non_registry():
    assert "JX022" not in rules_of("""
        import collections

        def setup(reg):
            return reg.histogram("x_seconds", "doc")

        def tally(items):
            for it in items:
                c = collections.Counter(it)     # not a registry lookup
            return c
    """)


def test_jx022_pragma_suppresses():
    assert "JX022" not in rules_of("""
        def run(batches, reg):
            for b in batches:
                reg.counter("x_total", "d").inc()  # graftlint: disable=JX022  (cold loop)
    """)


# ---------------------------------------------------------------- JX023
_GENERATION_PATH = "deeplearning4j_tpu/generation/fix.py"


def test_jx023_positive_per_token_syncs_in_decode_scope():
    src = """
        import numpy as np

        def decode_tokens(model, tok, n):
            out = []
            for _ in range(n):
                logits = model.decode(tok)
                tok = float(logits)              # per-token host sync
                out.append(tok)
            return out

        def drain(engine):
            while engine.alive():
                dev = engine.poll()
                host = np.asarray(dev)           # per-token host sync
                yield host

        def emit(rows):
            for r in rows:
                yield r.item()                   # per-token host sync
    """
    for path in (_GENERATION_PATH, _SERVING_PATH):
        fs = lint_source(textwrap.dedent(src), path)
        assert sum(f.rule == "JX023" for f in fs) == 3, path


def test_jx023_negative_out_of_scope_path():
    # the identical per-token sync outside generation//serving/ is JX003
    # territory (training loops) or legal ETL — JX023 stays silent
    assert "JX023" not in rules_at("""
        import numpy as np

        def decode_tokens(model, tok, n):
            out = []
            for _ in range(n):
                tok = float(model.decode(tok))
                out.append(tok)
            return out
    """, "deeplearning4j_tpu/data/fix.py")


def test_jx023_negative_batched_materialization_at_step_boundary():
    # the engine contract: ONE np.asarray per decode step for the whole
    # slot batch, host-side int() on the already-materialized array rows
    assert "JX023" not in rules_at("""
        import numpy as np

        def decode_step(model, toks, caches, occupants):
            out_dev, caches = model.decode(toks, caches)
            out = np.asarray(out_dev)            # once per STEP: legal
            for slot, req in occupants.items():
                req.emit(int(out[slot]))         # host array row, no sync
            return caches
    """, _GENERATION_PATH)


def test_jx023_negative_host_only_module_and_list_etl():
    # pure-host modules (no jax/numpy import) have nothing to sync on,
    # and np.asarray FROM a list literal is host ETL, not a device fetch
    assert "JX023" not in rules_at("""
        def drain(q):
            while True:
                ev = q.get()
                yield ev.item()
    """, _GENERATION_PATH)
    assert "JX023" not in rules_at("""
        import numpy as np

        def pack(rows):
            for r in rows:
                yield np.asarray([1, 2, 3])
    """, _GENERATION_PATH)


def test_jx023_pragma_suppresses():
    assert "JX023" not in rules_at("""
        import numpy as np

        def warmup(model, buckets):
            for b in buckets:
                np.asarray(model.forward(b))  # graftlint: disable=JX023  (warmup: block per compile)
    """, _SERVING_PATH)


# ---------------------------------------------------------------- JX024
_PARALLEL_PATH = "deeplearning4j_tpu/parallel/fix.py"
_NN_PATH = "deeplearning4j_tpu/nn/fix.py"


def test_jx024_positive_full_pytree_materialization_in_step_loop():
    src = """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def fit(step, params, opt_state, batches):
            for x, y in batches:
                params, opt_state = step(params, opt_state, x, y)
                host = np.asarray(params)        # full-model host copy
            return params

        def monitor(step, params, batches):
            for b in batches:
                params = step(params, b)
                snap = jax.device_get(params)    # full-model host copy
                print(snap)

        def gathered_update(params, grads, steps):
            i = 0
            while i < steps:
                full = jax.lax.all_gather(params, "data")  # resident global params
                params = full - 0.1 * grads
                i += 1
            return params
    """
    for path in (_PARALLEL_PATH, _NN_PATH):
        fs = lint_source(textwrap.dedent(src), path)
        assert sum(f.rule == "JX024" for f in fs) == 3, path


def test_jx024_negative_out_of_scope_and_boundaries():
    # same spellings outside parallel//nn/ are other rules' territory
    assert "JX024" not in rules_at("""
        import numpy as np

        def fit(step, params, batches):
            for b in batches:
                params = step(params, b)
                np.asarray(params)
    """, "deeplearning4j_tpu/serving/fix.py")
    # checkpoint/serialize boundaries materialize OUTSIDE the loop, and
    # per-batch materialization of non-params values stays legal
    assert "JX024" not in rules_at("""
        import jax
        import numpy as np

        def fit(step, params, batches):
            for x, y in batches:
                params, loss = step(params, x, y)
                score = float(loss)
            return np.asarray(params)            # once, at the boundary

        def collect(step, params, batches):
            out = []
            for b in batches:
                params, logits = step(params, b)
                out.append(np.asarray(logits))   # activations, not params
            return out
    """, _PARALLEL_PATH)


def test_jx024_pragma_suppresses():
    assert "JX024" not in rules_at("""
        import jax

        def debug_fit(step, params, batches):
            for b in batches:
                params = step(params, b)
                jax.device_get(params)  # graftlint: disable=JX024  (debug digest per step)
    """, _PARALLEL_PATH)


# ---------------------------------------------------------------- JX025
_FT_PATH = "deeplearning4j_tpu/faulttolerance/fix.py"


def test_jx025_positive_unbudgeted_rendezvous_waits():
    src = """
        import time

        def wait_for_markers(stage, expected):
            while True:
                have = scan(stage)
                if not (expected - have):
                    break
                time.sleep(0.05)          # no deadline, no budget

        def lease_poll(store, want):
            missing = list(want)
            while missing:
                live = store.all_leases()
                missing = [w for w in want if w not in live]
                time.sleep(0.1)
    """
    for path in (_FT_PATH, "deeplearning4j_tpu/parallel/fix.py"):
        fs = lint_source(textwrap.dedent(src), path)
        assert sum(f.rule == "JX025" for f in fs) == 2, path


def test_jx025_negative_budgeted_and_cancellable_waits():
    # deadline-bounded, stop-event, drain-until-empty, attempt-budgeted
    # and out-of-scope waits all stay legal
    assert "JX025" not in rules_at("""
        import time

        def wait_for_markers(stage, expected, timeout_s):
            deadline = time.time() + timeout_s
            while True:
                if not (expected - scan(stage)):
                    break
                if time.time() > deadline:
                    raise TimeoutError("barrier timed out")
                time.sleep(0.05)

        def heartbeat(stop, interval):
            while not stop.wait(interval):
                renew()

        def beat_with_body_check(stop, broker):
            while True:
                broker.publish(b"hb")
                if stop.wait(0.5):
                    return

        def drain(sub):
            while True:
                payload = sub.poll(timeout=0.001)
                if payload is None:
                    break
                handle(payload)

        def retry(policy, worker):
            attempt = 0
            while attempt < policy.max_retries:
                attempt += 1
                policy.sleep(attempt, worker)
    """, _FT_PATH)
    # same spelling outside faulttolerance//parallel/ is out of scope
    assert "JX025" not in rules_at("""
        import time

        def wait(flag):
            while True:
                if flag():
                    break
                time.sleep(0.05)
    """, "deeplearning4j_tpu/serving/fix.py")


def test_jx025_pragma_suppresses():
    assert "JX025" not in rules_at("""
        import time

        def wait_forever(flag):
            while True:
                if flag():
                    break
                time.sleep(0.05)  # graftlint: disable=JX025  (test rig: the driver kills us)
    """, _FT_PATH)


# ---------------------------------------------------------------- JX026
_NN_PATH = "deeplearning4j_tpu/nn/fix.py"


def test_jx026_positive_debug_and_callbacks_in_package_module():
    src = """
        import jax
        from jax import pure_callback
        from jax.experimental import io_callback

        def step(params, x):
            jax.debug.print("x={x}", x=x)            # leftover debug
            jax.debug.breakpoint()                   # leftover debug
            y = pure_callback(host_fn, spec, x)      # host round-trip
            z = io_callback(logger, None, y)         # host round-trip
            return jax.pure_callback(host_fn, spec, z)
    """
    fs = lint_source(textwrap.dedent(src), _NN_PATH)
    assert sum(f.rule == "JX026" for f in fs) == 5


def test_jx026_positive_debug_module_aliases():
    # both spellings of a jax.debug module alias must fire: the
    # from-import and `import jax.debug as jdbg` (which binds the alias
    # name, so the dotted jax.debug.* branch never sees it)
    for imp, call in (("from jax import debug", "debug.print"),
                      ("import jax.debug as jdbg", "jdbg.print")):
        src = f"""
            {imp}

            def step(x):
                {call}("x={{x}}", x=x)
                return x
        """
        fs = lint_source(textwrap.dedent(src), _NN_PATH)
        assert sum(f.rule == "JX026" for f in fs) == 1, imp


def test_jx026_negative_test_modules_out_of_scope():
    # printing tracers is what debugging a test looks like — every
    # test-shaped path stays legal
    src = """
        import jax

        def test_step(x):
            jax.debug.print("x={x}", x=x)
            return x
    """
    for path in ("tests/test_step.py", "deeplearning4j_tpu/test_fix.py",
                 "tests/conftest.py"):
        assert "JX026" not in rules_at(src, path)


def test_jx026_negative_unrelated_names():
    # a user-defined pure_callback (no jax import of it) and non-debug
    # jax attrs don't fire
    assert "JX026" not in rules_at("""
        import jax

        def pure_callback(fn, spec, x):
            return fn(x)

        def step(x):
            y = pure_callback(abs, None, x)
            return jax.device_get(y)
    """, _NN_PATH)


def test_jx026_pragma_suppresses():
    src = """
        import jax

        def evaluate(x):
            jax.debug.print("eval={x}", x=x)  # graftlint: disable=JX026  (documented eval-only trace hook)
            return x
    """
    assert "JX026" not in {f.rule
                           for f in lint_source(textwrap.dedent(src),
                                                _NN_PATH)}


# ---------------------------------------------------------------- JX027
def test_jx027_positive_one_hot_matmul_lookup():
    src = """
        import jax
        import jax.numpy as jnp
        from jax.nn import one_hot

        def lookup(ids, W, vocab):
            a = jax.nn.one_hot(ids, vocab) @ W          # dense lookup
            b = one_hot(ids, vocab).T @ W               # transposed form
            c = W.T @ jax.nn.one_hot(ids, vocab)        # right operand
            return a + b.T + c.T
    """
    fs = lint_source(textwrap.dedent(src), _NN_PATH)
    assert sum(f.rule == "JX027" for f in fs) == 3


def test_jx027_positive_full_vocab_zeros_scatter():
    src = """
        import jax.numpy as jnp

        def dense_grad(rows, idx, n_in, dim):
            direct = jnp.zeros((n_in, dim)).at[idx].add(rows)
            buf = jnp.zeros((vocab_size, dim))
            hop = buf.at[idx].set(rows)                 # one-hop name
            return direct + hop
    """
    fs = lint_source(textwrap.dedent(src), _NN_PATH)
    assert sum(f.rule == "JX027" for f in fs) == 2


def test_jx027_positive_module_scope_and_jax_nn_import():
    # the two coverage gaps a review closed: `from jax import nn`
    # spells the same dense lookup, and a module/class-level scatter
    # is as dense as a function-local one
    src = """
        import jax.numpy as jnp
        from jax import nn

        DENSE = jnp.zeros((vocab_size, 16)).at[IDX].add(ROWS)

        class Table:
            cache = jnp.zeros((n_in, 8)).at[IDS].set(VALS)

        def lookup(ids, W, vocab):
            return nn.one_hot(ids, vocab) @ W
    """
    fs = lint_source(textwrap.dedent(src), _NN_PATH)
    assert sum(f.rule == "JX027" for f in fs) == 3


def test_jx027_negative_gather_and_small_buffers():
    # the gather path, a non-vocab zeros scatter, a one_hot without a
    # matmul, and a named one-hot matmul (kmeans' deliberate MXU
    # centroid sum) all stay legal
    assert "JX027" not in rules_at("""
        import jax
        import jax.numpy as jnp

        def ok(ids, W, points, bins, batch):
            z = W[ids]                                   # gather lookup
            hist = jnp.zeros((bins,)).at[ids].add(1.0)   # not vocab-sized
            oh = jax.nn.one_hot(ids, 4)                  # no matmul
            sums = oh.T @ points                         # named operand
            return z, hist, sums
    """, _NN_PATH)


def test_jx027_negative_test_modules_out_of_scope():
    src = """
        import jax
        import jax.numpy as jnp

        def test_dense_reference(ids, W, vocab):
            return jax.nn.one_hot(ids, vocab) @ W
    """
    for path in ("tests/test_embed.py", "tests/conftest.py"):
        assert "JX027" not in rules_at(src, path)


def test_jx027_pragma_suppresses():
    src = """
        import jax.numpy as jnp

        def to_dense(rows, idx, n_rows, dim):
            dense = jnp.zeros((n_rows, dim))
            return dense.at[idx].add(rows)  # graftlint: disable=JX027  (documented host-side interop densification)
    """
    assert "JX027" not in {f.rule
                           for f in lint_source(textwrap.dedent(src),
                                                _NN_PATH)}


# ---------------------------------------------------------------- JX028
def test_jx028_positive_every_stray_jit_spelling():
    # the four spellings the package sweep found: bare decorator,
    # functools.partial decorator, direct call, and the bare import
    src = """
        import functools
        import jax
        from jax import pmap

        @jax.jit
        def f(x):
            return x

        @functools.partial(jax.jit, static_argnames=("k",))
        def g(x, k):
            return x

        h = jax.jit(lambda x: x + 1)
    """
    fs = lint_source(textwrap.dedent(src), _NN_PATH)
    assert sum(f.rule == "JX028" for f in fs) == 4


def test_jx028_negative_compile_cache_and_tests_exempt():
    src = """
        import jax

        @jax.jit
        def f(x):
            return x
    """
    for path in ("deeplearning4j_tpu/nn/compile_cache.py",
                 "tests/test_fix.py", "tests/conftest.py"):
        assert "JX028" not in rules_at(src, path)


def test_jx028_negative_unrelated_jit_attributes():
    # a non-jax object's .jit attr and a user function named jit don't
    # fire; neither does routing through the sanctioned wrapper
    assert "JX028" not in rules_at("""
        from ..nn.compile_cache import InstrumentedJit

        def jit(fn):
            return fn

        def build(engine, step):
            prog = engine.jit(step)
            wrapped = jit(step)
            return InstrumentedJit(step, donate_argnums=(0,)), prog, wrapped
    """, _NN_PATH)


def test_jx028_pragma_suppresses():
    src = """
        import jax

        @jax.jit  # graftlint: disable=JX028  (one-shot capability probe)
        def probe(x):
            return x
    """
    assert "JX028" not in {f.rule
                           for f in lint_source(textwrap.dedent(src),
                                                _NN_PATH)}


# ---------------------------------------------------------------- JX029
def test_jx029_positive_fence_spellings_in_loops():
    # the three spellings: dotted through the jax alias, bare import,
    # and the array-method form — all inside for/while bodies
    src = """
        import jax
        from jax import block_until_ready

        def fit(batches, step):
            for x in batches:
                loss = step(x)
                jax.block_until_ready(loss)

        def drain(handles):
            while handles:
                block_until_ready(handles.pop())

        def decode(tokens, out):
            for t in tokens:
                out = out.block_until_ready()
            return out
    """
    fs = lint_source(textwrap.dedent(src), _NN_PATH)
    assert sum(f.rule == "JX029" for f in fs) == 3


def test_jx029_negative_outside_loop_profiler_and_tests():
    # a one-shot fence (no loop) never fires anywhere
    src_once = """
        import jax

        def probe(x):
            jax.block_until_ready(x)
            return x
    """
    assert "JX029" not in rules_at(src_once, _NN_PATH)
    # the sampled fence in the profiler, and test modules, are exempt
    src_loop = """
        import jax

        def fence_all(handles):
            for h in handles:
                jax.block_until_ready(h)
    """
    for path in ("deeplearning4j_tpu/observability/profiler.py",
                 "tests/test_fix.py", "tests/conftest.py"):
        assert "JX029" not in rules_at(src_loop, path)


def test_jx029_pragma_suppresses():
    src = """
        import jax

        def average(rounds):
            for avg in rounds:
                jax.block_until_ready(avg)  # graftlint: disable=JX029  (deliberate once-per-round timing sync)
    """
    assert "JX029" not in {f.rule
                           for f in lint_source(textwrap.dedent(src),
                                                _NN_PATH)}


# ---------------------------------------------------------------- JX030
def test_jx030_positive_tree_calls_and_comprehensions_in_loops():
    # dotted tree_util call, jax.tree short form, bare import, and the
    # params-like dict-comprehension rebuild — all inside loop bodies
    src = """
        import jax
        from jax.tree_util import tree_map

        def fit(batches, step, params):
            for x in batches:
                params = jax.tree_util.tree_map(lambda p: p, params)

        def drain(handles, grads):
            while handles:
                handles.pop()
                flat = jax.tree.leaves(grads)

        def refresh(workers, params):
            for w in workers:
                w.params = tree_map(lambda p: p + 0, params)

        def rebuild(batches, params):
            for x in batches:
                params = {k: v * 2 for k, v in params.items()}
    """
    fs = lint_source(textwrap.dedent(src), _NN_PATH)
    assert sum(f.rule == "JX030" for f in fs) == 4


def test_jx030_negative_header_once_per_fit_and_paths():
    # a loop HEADER traversal runs once — for x in tree_leaves(p) is the
    # canonical bytes-accounting idiom, not a per-step rebuild
    src_header = """
        import jax

        def nbytes(params):
            total = 0
            for l in jax.tree_util.tree_leaves(params):
                total += l.size
            return total
    """
    assert "JX030" not in rules_at(src_header, _NN_PATH)
    # outside a loop: placement happens once per fit
    src_once = """
        import jax

        def place(params, sharding):
            return jax.tree_util.tree_map(lambda p: p, params)
    """
    assert "JX030" not in rules_at(src_once, _NN_PATH)
    # hot-path scoping: the same loop body is legal outside nn//parallel/
    src_loop = """
        import jax

        def fold(rounds, params):
            for r in rounds:
                params = jax.tree_util.tree_map(lambda p: p, params)
    """
    for path in ("deeplearning4j_tpu/utils/fix.py",
                 "deeplearning4j_tpu/observability/fix.py",
                 "tests/test_fix.py"):
        assert "JX030" not in rules_at(src_loop, path)
    # a comprehension over a non-tree name stays silent
    src_other = """
        def fold(rounds, rows):
            for r in rounds:
                out = [c * 2 for c in rows]
    """
    assert "JX030" not in rules_at(src_other, _NN_PATH)


def test_jx030_pragma_suppresses():
    src = """
        import jax

        def average(rounds, params):
            for r in rounds:
                params = jax.tree_util.tree_map(lambda p: p, params)  # graftlint: disable=JX030  (once per averaging round, not per step)
    """
    assert "JX030" not in {f.rule
                           for f in lint_source(textwrap.dedent(src),
                                                _NN_PATH)}


# ---------------------------------------------------------------- JX031
def test_jx031_positive_per_block_transfers():
    # per-block device traffic in all three spellings: .item() per table
    # entry, device_put per block of a table-iterating loop, and
    # device_get subscripting the table inside a while loop
    src = """
        import jax
        import numpy as np

        def gather(tables, slot, n, kv):
            out = []
            for i in range(n):
                out.append(kv[tables[slot, i].item()])
            return out

        def upload(table_row, pool):
            for blk in table_row:
                jax.device_put(blk)

        def drain(tables, pending):
            while pending:
                pending.pop()
                row = jax.device_get(tables[0])
    """
    fs = lint_source(textwrap.dedent(src), _GENERATION_PATH)
    assert sum(f.rule == "JX031" for f in fs) == 3


def test_jx031_negative_whole_table_bookkeeping_and_paths():
    # the engine's contract: the WHOLE table ships once per program call
    # (outside any loop), and host-side allocator bookkeeping loops over
    # tables never touch the device — both stay silent
    src_ok = """
        import jax
        import numpy as np

        def step(fn, caches, tables, pos):
            return fn(caches, tables.copy(), pos.copy())

        def release(tables, slot, refs):
            for blk in tables[slot]:
                refs[int(blk)] -= 1
    """
    assert "JX031" not in rules_at(src_ok, _GENERATION_PATH)
    # a .item() in a loop NOT touching a table is JX023's business
    src_item = """
        import jax

        def emit(toks):
            for t in toks:
                yield t.item()
    """
    assert "JX031" not in rules_at(src_item, _GENERATION_PATH)
    # path scoping: identical per-block code outside generation/ (and in
    # generation tests) is out of scope
    src_loop = """
        import jax

        def upload(table_row):
            for blk in table_row:
                jax.device_put(blk)
    """
    for path in ("deeplearning4j_tpu/nn/fix.py",
                 "tests/test_generation.py"):
        assert "JX031" not in rules_at(src_loop, path)


def test_jx031_pragma_suppresses():
    src = """
        import jax

        def dump(tables, slot):
            rows = []
            for i in range(tables.shape[1]):
                rows.append(tables[slot, i].item())  # graftlint: disable=JX031  (debug dump tool, not the request path)
            return rows
    """
    assert "JX031" not in {f.rule
                           for f in lint_source(textwrap.dedent(src),
                                                _GENERATION_PATH)}


# ---------------------------------------------------------------- JX032
def test_jx032_positive_lock_held_dispatch():
    # three dispatch classes under three lock spellings: engine entry
    # point under self._lock, fleet-wide swap under a dotted fleet
    # lock, HTTP client verb under a session lock
    src = """
        class Router:
            def route(self, x):
                with self._lock:
                    return self.best.engine.predict(x)

            def roll(self, model):
                with self.fleet._fleet_lock:
                    for r in self.fleet.replicas:
                        r.engine.hot_swap(model)

            def relay(self, sess, body):
                with sess.lock:
                    return sess.client.post("/generate", body)
    """
    fs = lint_source(textwrap.dedent(src), _SERVING_PATH)
    assert sum(f.rule == "JX032" for f in fs) == 3


def test_jx032_negative_snapshot_then_dispatch_and_paths():
    # the fleet idiom: pick the replica under the lock, dispatch
    # outside it — and O(1) bookkeeping under the lock stays legal
    src_ok = """
        class Router:
            def route(self, x):
                with self._lock:
                    target = min(self.replicas, key=lambda r: r.load())
                    target.inflight += 1
                return target.engine.predict(x)

            def migrate(self, sess, state):
                with sess.lock:
                    sess.epoch += 1
                    sess.replica.engine.import_session(state)
    """
    assert "JX032" not in rules_at(src_ok, _SERVING_PATH)
    # a with block that is not a lock (file handle) is out of scope
    src_file = """
        class Snap:
            def dump(self, path):
                with open(path) as fh:
                    return self.engine.predict(fh.read())
    """
    assert "JX032" not in rules_at(src_file, _SERVING_PATH)
    # path scoping: identical code outside serving/ (and in serving
    # tests) is out of scope
    src_held = """
        class Router:
            def route(self, x):
                with self._lock:
                    return self.best.engine.predict(x)
    """
    for path in ("deeplearning4j_tpu/generation/fix.py",
                 "tests/test_serving.py"):
        assert "JX032" not in rules_at(src_held, path)


def test_jx032_pragma_suppresses():
    src = """
        class Router:
            def drain(self, x):
                with self._lock:
                    return self.solo.engine.predict(x)  # graftlint: disable=JX032  (single-replica drain mode, fleet already quiesced)
    """
    assert "JX032" not in {f.rule
                           for f in lint_source(textwrap.dedent(src),
                                                _SERVING_PATH)}


# ---------------------------------------------------------------- JX018
def test_jx018_positive_unguarded_increment_from_thread():
    got = findings("""
        import threading

        class Engine:
            def __init__(self):
                self.batches = 0
                self._t = threading.Thread(target=self._loop, daemon=True)
                self._t.start()

            def _loop(self):
                self._note()

            def _note(self):
                self.batches += 1        # dispatcher thread, no lock

            def stats(self):
                return self.batches      # caller thread
    """, select=["JX018"])
    assert len(got) == 1 and got[0].rule == "JX018"


def test_jx018_positive_inconsistent_guarding():
    # guarded write in one method, bare write in another: the discipline
    # exists and this mutation skips it
    assert "JX018" in rules_of("""
        import threading

        class Holder:
            def __init__(self):
                self._lock = threading.Lock()
                self.version = 0
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                with self._lock:
                    self.version += 1

            def reset(self):
                self.version = 0         # skips the lock others hold
    """)


def test_jx018_negative_consistent_guard_and_no_threads():
    assert "JX018" not in rules_of("""
        import threading

        class Guarded:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                with self._lock:
                    self.n += 1

            def read(self):
                with self._lock:
                    return self.n

        class SingleThreaded:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1              # no thread entry: legal
    """)


def test_jx018_negative_aliased_import_and_injected_lock():
    # lock recognition must resolve `import threading as th` exactly like
    # spawn detection does, and an injected lock (ctor parameter) is a
    # lock because it is USED as one — neither may fire on guarded code
    assert "JX018" not in rules_of("""
        import threading as th

        class AliasGuarded:
            def __init__(self):
                self._lock = th.Lock()
                self.n = 0
                self._t = None

            def start(self):
                self._t = th.Thread(target=self._run, daemon=True)
                self._t.start()

            def _run(self):
                with self._lock:
                    self.n += 1

            def read(self):
                with self._lock:
                    return self.n

        class InjectedLock:
            def __init__(self, lock):
                self._lock = lock
                self.n = 0
                self._t = None

            def start(self):
                import threading
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()

            def _run(self):
                with self._lock:
                    self.n += 1

            def read(self):
                with self._lock:
                    return self.n
    """)


def test_jx020_positive_under_aliased_import():
    # the lock-order graph must see th.Lock() attrs or aliased modules
    # silently disable deadlock detection
    assert "JX020" in rules_of("""
        import threading as th

        class AB:
            def __init__(self):
                self.a = th.Lock()
                self.b = th.Lock()

            def fwd(self):
                with self.a:
                    with self.b:
                        pass

            def bwd(self):
                with self.b:
                    with self.a:
                        pass
    """)


def test_jx018_negative_thread_private_and_safe_attrs():
    assert "JX018" not in rules_of("""
        import queue
        import threading

        class Private:
            def __init__(self):
                self.progress = 0
                self.results = queue.Queue(8)   # thread-safe primitive
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                self.progress += 1       # only the thread touches it
                self.results.put(1)
    """)


def test_jx018_positive_handler_shared_server_counter():
    # handler classes run one instance per connection: `self` is private
    # but the server ref is shared across concurrent request threads
    assert "JX018" in rules_of("""
        class _H(JsonHandler):
            server_ref = None

            def do_POST(self):
                srv = self.server_ref
                srv.failures += 1
    """)


def test_jx018_negative_handler_local_receiver():
    # a receiver built fresh in the handler is single-threaded
    assert "JX018" not in rules_of("""
        class _H(JsonHandler):
            def do_POST(self):
                r = Reader(self.rfile)
                r.off += 4
    """)


def test_jx018_pragma_suppresses():
    assert "JX018" not in rules_of("""
        import threading

        class E:
            def __init__(self):
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                self.n += 1  # graftlint: disable=JX018  (monotonic, torn reads fine)

            def read(self):
                return self.n
    """)


# ---------------------------------------------------------------- JX019
def test_jx019_positive_self_attr_thread_never_joined():
    got = findings("""
        import threading

        class Pump:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def _run(self):
                pass

            def close(self):
                pass                     # no join anywhere
    """, select=["JX019"])
    assert len(got) == 1


def test_jx019_positive_local_thread_and_chained_start():
    got = findings("""
        import threading

        def fire_and_forget(fn):
            t = threading.Thread(target=fn)
            t.start()                    # local, never joined

        def also_leaks(fn):
            threading.Thread(target=fn).start()   # unbound handle
    """, select=["JX019"])
    assert len(got) == 2


def test_jx019_positive_timer_without_cancel():
    assert "JX019" in rules_of("""
        import threading

        class Delayed:
            def arm(self):
                self._timer = threading.Timer(5.0, self._fire)
                self._timer.start()

            def _fire(self):
                pass
    """)


def test_jx019_negative_daemon_joined_escaping_and_submit():
    assert "JX019" not in rules_of("""
        import threading

        class Clean:
            def start(self):
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()
                self._w = threading.Thread(target=self._run)
                self._w.start()

            def _run(self):
                pass

            def close(self):
                self._w.join()

        def handed_to_caller(fn):
            t = threading.Thread(target=fn)
            t.start()
            return t                     # caller's to join

        def pooled(pool, fn):
            pool.submit(fn)              # executor owns the lifecycle
    """)


def test_jx019_negative_computed_daemon_flag_is_unresolvable():
    # daemon=<expr> can't be resolved statically: the fact drops on the
    # quiet side (possibly-daemon), never a loud false positive
    assert "JX019" not in rules_of("""
        import threading

        class Configurable:
            def __init__(self, cfg):
                self._cfg = cfg

            def start(self, flag):
                self._t = threading.Thread(target=self._run,
                                           daemon=flag)
                self._t.start()
                self._u = threading.Thread(target=self._run)
                self._u.daemon = self._cfg.daemonize
                self._u.start()

            def _run(self):
                pass
    """)


def test_jx019_negative_double_buffer_alias_join():
    # the CheckpointManager idiom: the handle swaps through a local
    # before joining — still a join on the teardown path
    assert "JX019" not in rules_of("""
        import threading

        class Writer:
            def save(self):
                t = threading.Thread(target=self._write)
                self._worker = t
                t.start()

            def _write(self):
                pass

            def wait(self):
                t, self._worker = self._worker, None
                if t is not None:
                    t.join()
    """)


def test_jx019_pragma_suppresses():
    assert "JX019" not in rules_of("""
        import threading

        def spin(fn):
            t = threading.Thread(target=fn)  # graftlint: disable=JX019  (process-lifetime pump)
            t.start()
    """)


# ---------------------------------------------------------------- JX020
def test_jx020_positive_opposite_nesting_same_class():
    got = findings("""
        import threading

        class Transfer:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def debit(self):
                with self._a:
                    with self._b:
                        pass

            def credit(self):
                with self._b:
                    with self._a:
                        pass
    """, select=["JX020"])
    assert len(got) == 1
    assert "cycle" in got[0].message


def test_jx020_positive_cross_class_one_hop_call():
    # A holds its lock while calling into B (which takes B's lock); B
    # holds its lock while calling back into A — opposite orders across
    # two classes, resolved through constructor-typed attributes
    assert "JX020" in rules_of("""
        import threading

        class A:
            def __init__(self):
                self._la = threading.Lock()
                self._b = B()

            def fwd(self):
                with self._la:
                    self._b.take_b()

            def take_a(self):
                with self._la:
                    pass

        class B:
            def __init__(self):
                self._lb = threading.Lock()
                self._a = A()

            def take_b(self):
                with self._lb:
                    pass

            def back(self):
                with self._lb:
                    self._a.take_a()
    """)


def test_jx020_negative_consistent_order_and_single_lock():
    assert "JX020" not in rules_of("""
        import threading

        class Ordered:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def two(self):
                with self._a:
                    with self._b:
                        pass

            def three(self):
                with self._b:
                    pass                 # alone: no edge back
    """)


# ---------------------------------------------------------------- JX021
def test_jx021_positive_membership_outside_guard():
    got = findings("""
        import threading

        class Cache:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = {}

            def put(self, k, v):
                with self._lock:
                    self._d[k] = v

            def get(self, k):
                if k in self._d:         # unguarded check...
                    return self._d[k]    # ...then act
                return None
    """, select=["JX021"])
    assert len(got) == 1


def test_jx021_negative_pair_under_guard_or_no_discipline():
    assert "JX021" not in rules_of("""
        import threading

        class Guarded:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = {}

            def put(self, k, v):
                with self._lock:
                    self._d[k] = v

            def get(self, k):
                with self._lock:
                    if k in self._d:
                        return self._d[k]
                return None

        class NoLocks:
            def __init__(self):
                self._d = {}

            def get(self, k):
                if k in self._d:         # no inferred guard: no
                    return self._d[k]    # discipline to violate
                return None
    """)


def test_jx021_positive_qsize_gated_get():
    assert "JX021" in rules_of("""
        import queue
        import threading

        class Drain:
            def __init__(self):
                self._q = queue.Queue(8)
                t = threading.Thread(target=self._run, daemon=True)
                t.start()

            def _run(self):
                pass

            def take(self):
                if not self._q.empty():
                    return self._q.get()   # sibling consumer can win
                return None
    """)


def test_jx021_negative_get_nowait_drain():
    assert "JX021" not in rules_of("""
        import queue
        import threading

        class Drain:
            def __init__(self):
                self._q = queue.Queue(8)
                t = threading.Thread(target=self._run, daemon=True)
                t.start()

            def _run(self):
                pass

            def take(self):
                try:
                    return self._q.get_nowait()
                except queue.Empty:
                    return None
    """)


def test_jx021_pragma_suppresses():
    assert "JX021" not in rules_of("""
        import threading

        class Cache:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = {}

            def put(self, k, v):
                with self._lock:
                    self._d[k] = v

            def get(self, k):
                if k in self._d:  # graftlint: disable=JX021  (single-threaded reader)
                    return self._d[k]
                return None
    """)


# ------------------------------------ whole-program analysis layer units
def _program_of(src: str, path: str = "mod.py"):
    from tools.graftlint.analysis import analyze_module
    from tools.graftlint.program import build_program
    return build_program([analyze_module(textwrap.dedent(src), path)])


def _entries(prog, cls_name: str):
    cls = next(c for c in prog.classes if c.name == cls_name)
    return {getattr(f, "name", "<lambda>") for f in cls.entry_funcs}


def test_thread_entry_direct_target_and_closure():
    prog = _program_of("""
        import threading

        class W:
            def go(self):
                threading.Thread(target=self._loop).start()

            def _loop(self):
                self._helper()

            def _helper(self):
                pass

            def untouched(self):
                pass
    """)
    assert _entries(prog, "W") == {"_loop", "_helper"}


def test_thread_entry_bound_method_one_hop_wrapper_and_submit():
    prog = _program_of("""
        import threading

        class W:
            def a(self):
                fn = self._loop_a        # one-hop alias
                threading.Thread(target=fn).start()

            def b(self, pool):
                pool.submit(self._loop_b)

            def c(self):
                def runner():
                    self._loop_c()
                t = threading.Timer(1.0, runner)
                t.start()
                t.cancel()

            def _loop_a(self):
                pass

            def _loop_b(self):
                pass

            def _loop_c(self):
                pass
    """)
    got = _entries(prog, "W")
    assert {"_loop_a", "_loop_b", "_loop_c", "runner"} <= got


def test_thread_entry_cross_class_constructor_typed():
    prog = _program_of("""
        import threading

        class Worker:
            def run(self):
                self.steps = 1

            def idle(self):
                pass

        def launch():
            w = Worker()
            threading.Thread(target=w.run).start()
    """)
    assert _entries(prog, "Worker") == {"run"}


def test_guarded_by_with_scope_and_try_finally():
    prog = _program_of("""
        import threading

        class G:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0
                self.m = 0

            def with_scope(self):
                with self._lock:
                    self.n += 1

            def try_finally(self):
                self._lock.acquire()
                try:
                    self.m += 1
                finally:
                    self._lock.release()

            def after_release(self):
                self._lock.acquire()
                self._lock.release()
                self.m += 1              # NOT guarded here
    """)
    cls = prog.classes[0]
    assert cls.guards("n") == {"_lock"}
    assert cls.guards("m") == {"_lock"}
    unguarded_m = [a for a in cls.accesses
                   if a.attr == "m" and a.write and not a.held
                   and not a.in_init]
    assert len(unguarded_m) == 1         # only the after-release write


def test_guarded_by_property_aliased_lock():
    prog = _program_of("""
        import threading

        class G:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            @property
            def lock(self):
                return self._lock

            def bump(self):
                with self.lock:          # alias guards the same token
                    self.n += 1
    """)
    assert prog.classes[0].guards("n") == {"_lock"}


def test_lock_order_graph_edges_and_cycle_detection():
    from tools.graftlint.program import find_lock_cycles
    prog = _program_of("""
        import threading

        class T:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def fwd(self):
                with self._a:
                    with self._b:
                        pass

            def rev(self):
                with self._b:
                    with self._a:
                        pass
    """)
    edges = prog.lock_edges()
    labels = {(a.label(), b.label()) for a, b, _, _ in edges}
    assert ("T._a", "T._b") in labels and ("T._b", "T._a") in labels
    cycles = find_lock_cycles(edges)
    assert len(cycles) == 1
    assert {n.label() for n in cycles[0][0]} == {"T._a", "T._b"}


def test_lock_order_no_cycle_negative():
    from tools.graftlint.program import find_lock_cycles
    prog = _program_of("""
        import threading

        class T:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self._c = threading.Lock()

            def chain(self):
                with self._a:
                    with self._b:
                        with self._c:
                            pass
    """)
    assert find_lock_cycles(prog.lock_edges()) == []


# ------------------------------------------------------------- pragmas
def test_pragma_same_line_suppresses():
    assert "JX007" not in rules_of("""
        def f():
            try:
                return 1
            except:  # graftlint: disable=JX007
                return 2
    """)


def test_pragma_standalone_line_suppresses_next_line():
    assert "JX008" not in rules_of("""
        # graftlint: disable=JX008
        def f(a, xs=[]):
            return a
    """)


def test_pragma_disable_file():
    src = """
        # graftlint: disable-file=JX007
        def f():
            try:
                return 1
            except:
                return 2

        def g():
            try:
                return 3
            except:
                return 4
    """
    assert "JX007" not in rules_of(src)


def test_pragma_only_suppresses_named_rule():
    got = rules_of("""
        def f(a, xs=[]):
            try:
                return a
            except:  # graftlint: disable=JX008
                return xs
    """)
    assert "JX007" in got        # pragma names the WRONG rule
    assert "JX008" in got        # JX008 is on the def line, not here


# ------------------------------------------------------------- baseline
def test_baseline_absorbs_exact_budget(tmp_path):
    src = textwrap.dedent("""
        def f():
            try:
                return 1
            except:
                return 2
    """)
    f = tmp_path / "m.py"
    f.write_text(src)
    found = lint_paths([str(f)])
    assert [x.rule for x in found] == ["JX007"]
    bl = Baseline.from_findings(found)
    assert bl.filter(found) == []
    # a SECOND bare except exceeds the budget
    f.write_text(src + textwrap.dedent("""
        def g():
            try:
                return 3
            except:
                return 4
    """))
    found2 = lint_paths([str(f)])
    assert len(found2) == 2
    assert len(bl.filter(found2)) == 1


def test_baseline_round_trips_through_json(tmp_path):
    bl = Baseline({"pkg/m.py::JX003": 2})
    p = tmp_path / "baseline.json"
    bl.save(str(p))
    loaded = Baseline.load(str(p))
    assert loaded.allowances == {"pkg/m.py::JX003": 2}
    assert Baseline.load(str(tmp_path / "missing.json")).allowances == {}


def test_baseline_reports_stale_entries(tmp_path):
    """Ratchet: allowances matching no finding come back as stale so the
    suppression can't outlive its bug and silently absorb a new one."""
    src = textwrap.dedent("""
        def f():
            try:
                return 1
            except:
                return 2
    """)
    f = tmp_path / "m.py"
    f.write_text(src)
    found = lint_paths([str(f)])
    import os
    key = f"{os.path.relpath(found[0].path)}::JX007".replace(os.sep, "/")
    live = Baseline({key: 1, "gone/file.py::JX003": 2})
    kept, stale = live.apply(found)
    assert kept == []
    assert stale == ["gone/file.py::JX003"]
    # an entry matching SOME findings is live even when over-budgeted
    over = Baseline({key: 5})
    kept, stale = over.apply(found)
    assert kept == [] and stale == []


def test_cli_stale_baseline_errors(tmp_path):
    # run FROM tmp_path so the fabricated key's path resolves against
    # the cwd, the way real repo-root runs resolve repo-relative keys
    clean = tmp_path / "ok.py"
    clean.write_text("def f(a, xs=None):\n    return a\n")
    bl = tmp_path / "baseline.json"
    Baseline({"gone/file.py::JX008": 1}).save(str(bl))
    env = dict(os.environ, PYTHONPATH=str(REPO_ROOT))
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint",
         "--baseline", str(bl), "ok.py"],
        capture_output=True, text=True, cwd=str(tmp_path), env=env)
    assert r.returncode == 2
    assert "stale baseline" in r.stderr
    assert "gone/file.py::JX008" in r.stderr


def test_cli_stale_ratchet_stands_down_outside_baseline_cwd(tmp_path):
    """Baseline keys are relative to the cwd they were written from; a
    run from a DIFFERENT directory cannot resolve them, so live
    allowances must not be escalated into exit-2 'stale' errors."""
    proj = tmp_path / "proj"
    proj.mkdir()
    f = proj / "m.py"
    f.write_text("def f(a, xs=[]):\n    return a\n")   # JX008 finding
    bl = proj / "baseline.json"
    # the live m.py key proves the cwd mismatch, which must also shield
    # the deleted-file key from being misjudged through the wrong cwd
    Baseline({"m.py::JX008": 1, "gone.py::JX019": 1}).save(str(bl))
    env = dict(os.environ, PYTHONPATH=str(REPO_ROOT))
    elsewhere = tmp_path / "elsewhere"
    elsewhere.mkdir()
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint",
         "--baseline", str(bl), str(proj)],
        capture_output=True, text=True, cwd=str(elsewhere), env=env)
    # the allowance can't absorb its finding from this cwd (findings
    # report, exit 1) — but it is live, not stale: no exit-2 escalation
    assert r.returncode == 1, r.stderr
    assert "stale" not in r.stderr


def test_cli_stale_ratchet_resolves_unlinted_keys_at_baseline_root(
        tmp_path):
    """An allowance for a file OUTSIDE the linted subset must be judged
    against the baseline's own root: from a parent-dir cwd a live file
    used to read as deleted (bogus exit 2), while a genuinely deleted
    file must still ratchet from any cwd."""
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "m.py").write_text("x = 1\n")
    (proj / "other.py").write_text("def f(a, xs=[]):\n    return a\n")
    bl = proj / "baseline.json"
    Baseline({"other.py::JX008": 1, "gone.py::JX019": 1}).save(str(bl))
    env = dict(os.environ, PYTHONPATH=str(REPO_ROOT))
    # lint ONLY m.py, from the parent directory
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint",
         "--baseline", str(bl), "proj/m.py"],
        capture_output=True, text=True, cwd=str(tmp_path), env=env)
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "gone.py::JX019" in r.stderr      # deleted: ratchets anywhere
    assert "other.py::JX008" not in r.stderr  # live: never misread


def test_cli_stale_ratchet_skipped_on_rule_subsets(tmp_path):
    """--select/--ignore runs never execute the rules some allowances
    target, so they must not classify those allowances as stale."""
    f = tmp_path / "m.py"
    f.write_text("def f(a, xs=[]):\n    return a\n")   # JX008 finding
    bl = tmp_path / "baseline.json"
    Baseline.from_findings(lint_paths([str(f)])).save(str(bl))
    # full run: allowance matches, clean exit
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint",
         "--baseline", str(bl), str(f)],
        capture_output=True, text=True, cwd=str(REPO_ROOT))
    assert r.returncode == 0, r.stderr
    # subset run that never executes JX008: the allowance matches
    # nothing, but must NOT be reported stale
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--select", "JX007",
         "--baseline", str(bl), str(f)],
        capture_output=True, text=True, cwd=str(REPO_ROOT))
    assert r.returncode == 0, r.stderr
    assert "stale" not in r.stderr


def test_cli_write_baseline_rejects_changed_only(tmp_path):
    f = tmp_path / "m.py"
    f.write_text("x = 1\n")
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--write-baseline",
         "--changed-only", "HEAD", str(f)],
        capture_output=True, text=True, cwd=str(REPO_ROOT))
    assert r.returncode == 2
    assert "full run" in r.stderr


# ------------------------------------------------------------------ CLI
def test_cli_text_and_json_and_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def f(a, xs=[]):\n    return a\n")
    env_root = str(REPO_ROOT)
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--no-baseline", str(bad)],
        capture_output=True, text=True, cwd=env_root)
    assert r.returncode == 1
    assert "JX008" in r.stdout
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--no-baseline",
         "--format", "json", str(bad)],
        capture_output=True, text=True, cwd=env_root)
    data = json.loads(r.stdout)
    assert data and data[0]["rule"] == "JX008"
    good = tmp_path / "good.py"
    good.write_text("def f(a, xs=None):\n    return a\n")
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--no-baseline", str(good)],
        capture_output=True, text=True, cwd=env_root)
    assert r.returncode == 0
    assert "clean" in r.stdout


def test_syntax_error_reported_not_crashed():
    got = lint_source("def f(:\n", "broken.py")
    assert [f.rule for f in got] == ["JX000"]


def test_cli_sarif_output(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def f(a, xs=[]):\n    return a\n")
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--no-baseline",
         "--format", "sarif", str(bad)],
        capture_output=True, text=True, cwd=str(REPO_ROOT))
    assert r.returncode == 1
    doc = json.loads(r.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftlint"
    assert [rule["id"] for rule in run["tool"]["driver"]["rules"]] == ["JX008"]
    res = run["results"][0]
    assert res["ruleId"] == "JX008"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("bad.py")
    assert loc["region"]["startLine"] == 1
    # clean run: valid SARIF with zero results, exit 0
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--no-baseline",
         "--format", "sarif", str(good)],
        capture_output=True, text=True, cwd=str(REPO_ROOT))
    assert r.returncode == 0
    assert json.loads(r.stdout)["runs"][0]["results"] == []


def test_cli_changed_only_lints_only_changed_files(tmp_path):
    """CI fast path: --changed-only <ref> restricts linting to files
    changed vs the ref (plus untracked), so a PR touching one module
    doesn't re-lint the world on every push."""
    env = {"GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
           "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t",
           "PATH": subprocess.os.environ["PATH"],
           "HOME": str(tmp_path),
           # the CLI resolves git against the LINTED tree (the tmp
           # repo), so the linter package must come in via PYTHONPATH
           "PYTHONPATH": str(REPO_ROOT)}

    def git(*args):
        r = subprocess.run(["git", *args], cwd=str(tmp_path), env=env,
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        return r

    git("init", "-q")
    (tmp_path / "stable.py").write_text("def f(a, xs=[]):\n    return a\n")
    (tmp_path / "touched.py").write_text("x = 1\n")
    git("add", "-A")
    git("commit", "-qm", "seed")
    # change one committed file, add one untracked file — both with
    # findings; the stable (committed, unchanged) file also has one
    (tmp_path / "touched.py").write_text("def g(b, m={}):\n    return b\n")
    (tmp_path / "fresh.py").write_text("def h(c, s=set()):\n    return c\n")
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--no-baseline",
         "--changed-only", "HEAD", "--format", "json",
         str(tmp_path)],
        capture_output=True, text=True, cwd=str(tmp_path), env=env)
    data = json.loads(r.stdout)
    hit_files = {Path(d["path"]).name for d in data}
    assert hit_files == {"touched.py", "fresh.py"}
    assert all(d["rule"] == "JX008" for d in data)
    # from a SUBDIRECTORY the same set must be found: ls-files scopes to
    # its cwd, so the CLI roots both git commands at the repo toplevel
    sub = tmp_path / "sub"
    sub.mkdir()
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--no-baseline",
         "--changed-only", "HEAD", "--format", "json", str(tmp_path)],
        capture_output=True, text=True, cwd=str(sub), env=env)
    data = json.loads(r.stdout)
    assert {Path(d["path"]).name for d in data} == {"touched.py",
                                                    "fresh.py"}
    # from inside a DIFFERENT git repo: git must be anchored at the
    # linted tree, not the cwd — resolving the cwd's repo used to diff
    # the wrong repo, intersect nothing, and report a false "clean"
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--no-baseline",
         "--changed-only", "HEAD", "--format", "json", str(tmp_path)],
        capture_output=True, text=True, cwd=str(REPO_ROOT), env=env)
    data = json.loads(r.stdout)
    assert {Path(d["path"]).name for d in data} == {"touched.py",
                                                    "fresh.py"}
    # with nothing changed, the run is clean without linting anything
    git("add", "-A")
    git("commit", "-qm", "all in")
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--no-baseline",
         "--changed-only", "HEAD", str(tmp_path)],
        capture_output=True, text=True, cwd=str(tmp_path), env=env)
    assert r.returncode == 0
    assert "no changed" in r.stdout


# ------------------------------------------------------------- the gate
def test_every_rule_has_docs():
    assert set(RULES) | set(PROGRAM_RULES) == set(RULE_DOCS)
    assert not set(RULES) & set(PROGRAM_RULES)
    assert len(RULES) == 28
    assert len(PROGRAM_RULES) == 4


@pytest.fixture(scope="module")
def package_lint():
    """ONE timed full-package run shared by the gate, ratchet, and
    wall-time budget tests (the run itself is the expensive part)."""
    t0 = time.perf_counter()
    found = lint_paths([str(PKG)])
    elapsed = time.perf_counter() - t0
    return found, elapsed


def test_package_is_clean_modulo_baseline(package_lint):
    """THE tier-1 gate: every future PR re-lints the whole package."""
    found, _ = package_lint
    kept, _stale = Baseline.load(str(BASELINE)).apply(found)
    assert kept == [], "\n".join(f.format() for f in kept)


def test_package_baseline_has_no_stale_entries(package_lint):
    """The ratchet: a baseline entry matching no finding means the
    suppressed bug was fixed — the allowance must be deleted."""
    found, _ = package_lint
    _, stale = Baseline.load(str(BASELINE)).apply(found)
    assert stale == [], stale


def test_full_package_lint_within_time_budget(package_lint):
    """The linter is part of the developer loop (tier-1 + bench): a rule
    addition that blows up wall time is a regression.  The budget is ~6x
    the current measured full-package time, so it trips on complexity
    blowups (quadratic walks), not CI jitter."""
    _, elapsed = package_lint
    assert elapsed < 25.0, f"full-package graftlint took {elapsed:.1f}s"


def test_baseline_is_near_empty():
    """The checked-in baseline must stay justified-in-review small."""
    bl = Baseline.load(str(BASELINE))
    assert sum(bl.allowances.values()) <= 5, bl.allowances


def test_no_bare_except_in_package():
    """ISSUE 1 acceptance: zero bare `except:` clauses in the package."""
    found = [f for f in lint_paths([str(PKG)], select=["JX007"])]
    assert found == [], "\n".join(f.format() for f in found)


def _docstrings(tree):
    """The string constants that are docstrings (prose, not code)."""
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)) and node.body:
            first = node.body[0]
            if isinstance(first, ast.Expr) and \
                    isinstance(first.value, ast.Constant) and \
                    isinstance(first.value.value, str):
                found.add(id(first.value))
    return found


def _into_tools(tree, kind):
    """Where a module's code reaches into the repo's ``tools/``: module
    names it imports (``import``, ``from``, ``import_module("...")``),
    or string constants outside docstrings that hold a ``tools`` path
    segment (``os.path.join(root, "tools", ...)``, ``"tools/x.json"``)."""
    hits = []
    if kind == "imports":
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module or ""]
            elif isinstance(node, ast.Call) and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str) and \
                    getattr(node.func, "attr", getattr(node.func, "id", "")) \
                    in ("import_module", "__import__"):
                names = [node.args[0].value]
            else:
                continue
            hits += [(node.lineno, n) for n in names
                     if n == "tools" or n.startswith("tools.")]
    else:
        prose = _docstrings(tree)
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str) and id(node) not in prose \
                    and "tools" in re.split(r"[\\/]", node.value):
                hits.append((node.lineno, node.value))
    return hits


@pytest.mark.parametrize("kind", ["imports", "paths"])
def test_the_package_reaches_nothing_under_tools(kind):
    """The arrow points one way: ``tools/`` (lint, audit, CLIs) may import
    the package, and the package runs without a checkout of ``tools/``."""
    found = []
    for path in sorted(PKG.rglob("*.py")):
        tree = ast.parse(path.read_text(encoding="utf-8"))
        found += [f"{path.relative_to(REPO_ROOT)}:{line}: {what!r}"
                  for line, what in _into_tools(tree, kind)]
    assert found == [], "\n".join(found)


# ----------------------------------------------- review-hardening fixes
def test_pragma_allows_trailing_justification():
    """The documented pragma form carries a justifying comment after the
    code list; it must still suppress."""
    assert "JX007" not in rules_of("""
        def f():
            try:
                return 1
            except:  # graftlint: disable=JX007   (cleanup must never raise)
                return 2
    """)
    assert "JX008" not in rules_of("""
        def f(a, xs=[], m={}):  # graftlint: disable=JX008, JX007 shared cache
            return a
    """)


def test_nonexistent_path_errors_instead_of_clean(tmp_path):
    with pytest.raises(FileNotFoundError):
        lint_paths([str(tmp_path / "no_such_dir")])


def test_non_py_file_argument_errors(tmp_path):
    f = tmp_path / "notes.txt"
    f.write_text("hello")
    with pytest.raises(ValueError, match="not a .py file"):
        lint_paths([str(f)])


def test_unknown_select_code_errors():
    with pytest.raises(ValueError, match="unknown rule code"):
        lint_source("x = 1\n", "m.py", select=["JXBOGUS"])
    with pytest.raises(ValueError, match="unknown rule code"):
        lint_source("x = 1\n", "m.py", ignore=["JX03"])


def test_cli_typo_path_exits_nonzero(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", str(tmp_path / "typo_dir")],
        capture_output=True, text=True, cwd=str(REPO_ROOT))
    assert r.returncode == 2
    assert "no such file" in r.stderr


def test_ui_numeric_style_fields_escaped_on_wire():
    """Declared-numeric style fields are NOT type-checked by the serde,
    so a string riding in where an int is expected must still escape."""
    from deeplearning4j_tpu.ui import (ComponentDiv, StyleDiv,
                                       component_from_json,
                                       component_to_json)
    payload = '"><script>alert(1)</script>'
    d = ComponentDiv(style=StyleDiv(width=100, float_value=payload))
    wire = component_to_json(d)
    out = component_from_json(wire).render()
    assert "<script>" not in out
    assert "&quot;&gt;&lt;script&gt;" in out
    # string smuggled into a declared-int field over the wire
    wire2 = wire.replace("100", json.dumps(payload).strip('"') and
                         json.dumps(payload))
    out2 = component_from_json(wire2).render()
    assert "<script>" not in out2
