"""What the chip's compiler says about the kernels of the main path, asked
here without the chip: libtpu compiles for a v5e that is described, not
attached.  Nothing runs, so these say nothing about results or speed —
only that the Pallas kernels and the LM train step are accepted at real
widths (tiling, scoped VMEM, partitioning across four chips).

The topology is described inside a module-scoped fixture that skips when
it cannot be; never at import (one process at a time may load the TPU
library, and every xdist worker imports every test file).  All of these
tests stay in this ONE file, so the worker that describes the topology is
the worker that runs them.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.observability.registry import default_registry
from deeplearning4j_tpu.ops import flash_attention as F
from deeplearning4j_tpu.ops import pallas_bn, pallas_lstm

HEAD_DIM = 64          # GPT-2-small: embed 768 / 12 heads


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as jcc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without the chip: keep the
    # cache off around these compiles.  And compile as the chip does, with
    # 64-bit mode off: tests/conftest.py turns it on for gradient checks,
    # and under it every Python scalar in a kernel body is an f64 constant
    # that Mosaic refuses to truncate ('tpu.truncf' (f64) -> f32).
    prev_cache = jax.config.jax_enable_compilation_cache
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    jcc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    jax.config.update("jax_enable_x64", prev_x64)
    jcc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("data",))


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _kernel_calls(compiled) -> dict:
    """How often each flash kernel is a custom call of the compiled
    program (a kernel's call carries its name in its scope)."""
    calls = [line for line in compiled.as_text().split("\n")
             if 'custom_call_target="tpu_custom_call"' in line]
    return {name: sum(f"/{name}/pallas_call" in c for c in calls)
            for name in F.FULL_KERNEL_NAMES}


def _remat_lines(compiled) -> list:
    """The instructions XLA rematerialized: those it named ``.remat``."""
    return [line for line in compiled.as_text().split("\n")
            if re.match(r"\s*(ROOT )?%?[\w.\-]*\.remat[\w.]* = ", line)]


def _head_repeats(compiled) -> list:
    """The instructions of an output layer that XLA rematerialized: those
    whose scope holds the layer's class."""
    return [line.split(" = ")[0].strip() for line in _remat_lines(compiled)
            if "OutputLayer/" in line]


def _head_chunks():
    c = default_registry().get("head_chunks_traced_total")
    return {} if c is None else {labels: child.value
                                 for labels, child in c.samples()}


def _flash_calls():
    """``{form of the keys: calls of flash_attention traced so far}``."""
    c = default_registry().get("flash_calls_traced_total")
    return dict.fromkeys(("whole", "parts"), 0) | (
        {} if c is None else {labels[0]: child.value
                              for labels, child in c.samples()})


def _fwd_groups():
    """``{groups of query rows a full block: calls traced so far}``."""
    c = default_registry().get("flash_fwd_groups_traced_total")
    return {} if c is None else {labels[0]: child.value
                                 for labels, child in c.samples()}


def _while_stacks(compiled, shape: str) -> int:
    """The most arrays of ``shape`` that one ``while`` of the compiled
    program carries: what the forward scan stacks for the backward."""
    return max(line.split(" while(")[0].count(shape)
               for line in compiled.as_text().split("\n")
               if " while(" in line)


def _qkv(sharding, rows, t, dtype=jnp.bfloat16):
    return (jax.ShapeDtypeStruct((rows, t, HEAD_DIM), dtype,
                                 sharding=sharding),) * 3


def _flash_rows(q, k, v, t):
    bq, bk = F.flash_blocks(t, t, HEAD_DIM)
    return F._flash(q, k, v, HEAD_DIM ** -0.5, True, bq, bk, False)


# The causal auto tiles (ops/flash_attention._auto_blocks, swept on a v5e
# under jax 0.9.0 / libtpu 0.0.34, PERF.md section 6, PR 28).  t=1024 at 96
# rows is chip_smoke.py's LM; at 48 rows it is the benchmark cell
# gpt2-medium.train-fit's own call (3 rows x 16 heads), so the tiles the
# benchmark runs are compiled for the v5e here; t=8192 fetches the longest
# block of keys one grid step holds (8192 x 64 bf16, 1 MiB a copy).
_FLASH_SHAPES = [(1024, 96), (8192, 12), (1024, 48)]


@pytest.mark.parametrize("t,rows", _FLASH_SHAPES)
def test_flash_forward_compiles_for_v5e(one_chip, t, rows):
    q, k, v = _qkv(one_chip, rows, t)
    c = jax.jit(lambda q, k, v: _flash_rows(q, k, v, t)).lower(
        q, k, v).compile()
    assert _custom_calls(c) == 1


@pytest.mark.parametrize("t,rows", _FLASH_SHAPES)
def test_flash_backward_dq_and_dkv_compile_for_v5e(one_chip, t, rows):
    """Both backward kernels (dq; dk/dv) beside the forward replay."""
    q, k, v = _qkv(one_chip, rows, t)

    def loss(q, k, v):
        return jnp.sum(_flash_rows(q, k, v, t).astype(jnp.float32))
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v)
    text = lowered.as_text()
    assert all(name in text for name in F.FULL_KERNEL_NAMES)
    assert _custom_calls(lowered.compile()) == 3


def test_flash_forward_compiles_in_query_groups_at_ouro_width(one_chip):
    """Ouro's call, [16, 8192, 128] bfloat16 causal: blocks of 1024 rows,
    of which the forward scores a full one as four groups of 256 query
    rows, each over all 1024 keys; the body fits scoped VMEM."""
    q = jax.ShapeDtypeStruct((1, 16, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    before = _fwd_groups()
    c = jax.jit(lambda q, k, v: F.flash_attention(q, k, v, causal=True)
                ).lower(q, q, q).compile()
    moved = {g: n - before.get(g, 0) for g, n in _fwd_groups().items()
             if n != before.get(g, 0)}
    assert moved == {"4": 1}
    assert _custom_calls(c) == 1


def _grid_pairs():
    """``{(pairs a head walks, steps of the square): calls traced}``."""
    c = default_registry().get("flash_grid_pairs_traced_total")
    return {} if c is None else {labels: child.value
                                 for labels, child in c.samples()}


def _walk_tables(compiled) -> dict:
    """``{kernel: [its calls' scalar-prefetched operand shapes]}``: the
    int32 columns of the walk's table each flash kernel call reads."""
    found = {}
    for line in compiled.as_text().split("\n"):
        name = re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call"', line)
        if ('custom_call_target="tpu_custom_call"' in line and name
                and name.group(1) in F.FULL_KERNEL_NAMES):
            found.setdefault(name.group(1), []).append(re.findall(
                r"s32\[\d+\]", re.search(
                    r"operand_layout_constraints=\{(.*?\})\}",
                    line).group(1)))
    return found


@pytest.mark.parametrize("keys", ["whole", "parts"])
def test_flash_kernels_walk_only_live_pairs_at_ouro_and_joyai_width(
        one_chip, keys):
    """Ouro's call, [16, 8192, 128], and JoyAI's, [32, 8192, 128 + 64 |
    128] with the keys in parts, causal in blocks of 1024 rows: each of
    the three kernels walks a table of the 36 live pairs of a head's 8 x 8
    blocks, not the 64 steps of the square, and compiles with it."""
    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    if keys == "whole":
        args = (spec(1, 16, 8192, 128),) * 3

        def attend(q, k, v):
            return F.flash_attention(q, k, v, causal=True)
    else:
        args = spec(1, 32, 8192, 192), spec(1, 32, 8192, 256), \
            spec(1, 1, 8192, 64)

        def attend(q, kv, ks):
            return F.flash_attention(q, kv=kv, k_shared=ks, causal=True)
    before = _grid_pairs()
    c = jax.jit(jax.grad(lambda *a: jnp.sum(attend(*a).astype(jnp.float32)),
                         argnums=(0, 1, 2))).lower(*args).compile()
    moved = {k: n - before.get(k, 0) for k, n in _grid_pairs().items()
             if n != before.get(k, 0)}
    assert moved == {("36", "64"): 1}
    assert _walk_tables(c) == {name: [["s32[36]"] * 4]
                               for name in F.FULL_KERNEL_NAMES}


def test_flash_float32_operands_compile_for_v5e(one_chip):
    """Serving runs on the f32 masters: the oracle forward of the serve
    phase hands the kernel float32 q/k/v."""
    q, k, v = _qkv(one_chip, 12, 1024, jnp.float32)
    c = jax.jit(lambda q, k, v: _flash_rows(q, k, v, 1024)).lower(
        q, k, v).compile()
    assert _custom_calls(c) == 1


def test_flash_partitions_over_four_chips(four_chips):
    """Mosaic kernels cannot be partitioned automatically; a jit over
    batch-sharded arguments (ShardedTrainer, ParallelWrapper) lowers only
    because ``flash_attention`` wraps them in a shard_map over the mesh it
    reads off its operand.  Each chip gets its own rows: no all-gather."""
    sh = NamedSharding(four_chips, P("data"))
    q = jax.ShapeDtypeStruct((8, 12, 1024, HEAD_DIM), jnp.bfloat16,
                             sharding=sh)

    def loss(q, k, v):
        return jnp.sum(F.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))
    c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).compile()
    text = c.as_text()
    assert _custom_calls(c) == 3
    assert "bf16[24,1024,64]" in text          # 2 of 8 batch rows x 12 heads
    assert "all-gather" not in text


# the LSTM helper keeps U [h, 4h] and the [b, h] carries in VMEM at any
# width: hidden 512 fits the v5e's 16 MiB scoped limit, hidden 1024 (U
# alone is 16 MiB) is refused.  ROADMAP D3 records the limit; it is not
# patched around.
@pytest.mark.parametrize("hidden,fits", [(512, True), (1024, False)])
def test_lstm_helper_compile_for_v5e(one_chip, hidden, fits):
    t, b = 64, 128

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    lowered = pallas_lstm._run.lower(
        f32(t, b, 4 * hidden), f32(hidden, 4 * hidden), f32(b, hidden),
        f32(b, hidden), interpret=False)
    if fits:
        assert _custom_calls(lowered.compile()) == 1
    else:
        with pytest.raises(Exception, match="vmem"):
            lowered.compile()


def test_bn_helper_compiles_for_v5e(one_chip):
    """The fused BN apply+relu kernel at ResNet50's widest early
    activation (batch 256, 56x56x256, bf16)."""
    shape = (256, 56, 56, 256)
    assert pallas_bn.supports(activation="relu", shape=shape, itemsize=2)
    m, c, _ = pallas_bn._lane_geometry(shape)

    def bf16(*s):
        return jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    compiled = pallas_bn._apply.lower(bf16(m, c), bf16(1, c), bf16(1, c),
                                      relu=True, interpret=False).compile()
    assert _custom_calls(compiled) == 1


@pytest.mark.parametrize("chips", [1, 4])
def test_lm_train_step_compiles_with_flash_kernels(topo, four_chips, chips,
                                                   monkeypatch):
    """The whole LM train step from shapes, at chip_smoke.py's widths
    (embed 768, 12 heads of 64, t=1024, vocab 32768; depth cut to 4
    blocks, which still scans), on one chip and ZeRO-3-sharded over four.
    'auto' asks ``jax.default_backend()``, which is the CPU here, so the
    test steers it; the lowered step must hold the forward and both
    backward kernels, and the compiler must accept it."""
    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.parallel.mesh import shard_params
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    net = TransformerLM(vocab_size=32768, seq_len=1024, embed=768,
                        n_layers=4, n_heads=12, attn_impl="auto",
                        sparse_labels=True,
                        compute_dtype="bfloat16").init()
    batch = 2 * chips
    if chips == 1:
        one = SingleDeviceSharding(topo.devices[0])

        def place(tree):
            return jax.tree_util.tree_map(lambda a: one, tree)
        batch_sh = rng_sh = one
    else:
        def place(tree):
            return shard_params(four_chips, tree)
        batch_sh = NamedSharding(four_chips, P("data", None))
        rng_sh = NamedSharding(four_chips, P())

    def shapes(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sh),
            tree, shardings)
    ids = jax.ShapeDtypeStruct((batch, 1024), jnp.int32, sharding=batch_sh)
    args = (shapes(net.params, place(net.params)),
            shapes(net.state, jax.tree_util.tree_map(lambda a: rng_sh,
                                                     net.state)),
            shapes(net.opt_state, place(net.opt_state)),
            jax.ShapeDtypeStruct(net._rng.shape, net._rng.dtype,
                                 sharding=rng_sh),
            ids, ids, None, None)
    lowered = net._get_jitted("train_step").audit_lower((args, {}))
    text = lowered.as_text()
    assert all(name in text for name in F.FULL_KERNEL_NAMES)
    compiled = lowered.compile()
    assert _custom_calls(compiled) == 3
    # one scan body each way, and each kernel once: the policy the scan
    # saves by (the block's names) does not replay the forward kernel in
    # the backward, on one chip or inside the shard_map of four
    assert _kernel_calls(compiled) == dict.fromkeys(F.FULL_KERNEL_NAMES, 1)
    if chips == 4:
        # parameters and optimizer state at about a quarter per chip
        whole = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                    for a in jax.tree_util.tree_leaves(
                        (net.params, net.opt_state)))
        per_chip = compiled.memory_analysis().argument_size_in_bytes
        assert 0.24 * whole < per_chip < 0.30 * whole

    # the same step with the block's names ignored (the scan then stacks
    # every intermediate its backward reads) needs more room for its
    # temporaries; built afresh, so that nothing traced is served again
    from deeplearning4j_tpu.nn.layers.attention import TransformerBlock
    from deeplearning4j_tpu.nn.multilayer import _build_train_step
    monkeypatch.setattr(TransformerBlock, "SAVED_NAMES", ())
    everything = jax.jit(_build_train_step(net.conf, net._tx, False),
                         donate_argnums=(0, 1, 2, 3)).lower(*args).compile()
    assert _kernel_calls(everything) == dict.fromkeys(F.FULL_KERNEL_NAMES, 1)
    named_temp = compiled.memory_analysis().temp_size_in_bytes
    all_temp = everything.memory_analysis().temp_size_in_bytes
    assert named_temp < 0.9 * all_temp, (named_temp, all_temp)
    # per chip two rows: the scan stacks the block's input and the stream
    # after the first add, and no third array of that shape
    stack = "bf16[4,2,1024,768]"
    assert _while_stacks(compiled, stack) == 2 < _while_stacks(everything,
                                                               stack)


# memory_stats()["bytes_limit"] of one TPU v5e, the compiler's 15.75 GiB
# (chip run, PR 32): what ``nn/scan_layers._device_limit`` reads there
V5E_BYTES_LIMIT = 16909336064
SPARE = int(0.1 * 2 ** 30)


def _evabyte_step(one_chip, monkeypatch, seq, fitting=None):
    """The benchmark's ``evabyte-4l`` train step (four blocks 4096 wide,
    32 heads of 128, MLP 11008, eight heads of 320 bytes, bfloat16 under
    ``cache_mode="remat"``) lowered from shapes for one described v5e at
    one row of ``seq`` bytes; the names its scanned run kept.  The device
    here is the CPU, which reports no limit: the test hands the v5e's."""
    from deeplearning4j_tpu.models import EvaByteLM
    from deeplearning4j_tpu.nn import scan_layers
    from deeplearning4j_tpu.nn.conf.updaters import Adam
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(scan_layers, "_device_limit",
                        lambda: V5E_BYTES_LIMIT)
    kept = []
    choose = fitting or scan_layers.fitting

    def spy(sizes, room):
        kept.append((choose(sizes, room), dict(sizes)))
        return kept[-1][0]
    monkeypatch.setattr(scan_layers, "fitting", spy)
    held = {}

    def built():
        # 821 M parameters and Adam's moments: shapes alone
        net = held["net"] = EvaByteLM(
            vocab_size=320, seq_len=seq, embed=4096, n_layers=4, n_heads=32,
            head_dim=128, ffn_hidden=11008, pred_heads=8, window=2048,
            chunk=16, attn_impl="auto", cache_mode="remat",
            compute_dtype="bfloat16",
            updater=Adam(learning_rate=3e-4)).init()
        return net.params, net.state, net.opt_state, net._rng
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(built))

    def batch(dtype, *tail):
        return jax.ShapeDtypeStruct((1, seq) + tail, dtype,
                                    sharding=one_chip)
    args += (batch(jnp.int32), batch(jnp.int32, 8), None,
             batch(jnp.float32, 8))
    lowered = held["net"]._get_jitted("train_step").audit_lower((args, {}))
    (names, sizes), = kept
    return lowered, names, sizes


def test_evabyte_step_fits_with_the_names_its_remat_run_keeps(
        one_chip, monkeypatch):
    """At the cell's 8192 bytes the run keeps some names and not all: the
    pooled three, q, k, v and the log-sum-exp; the MLP's two, each as large
    as q, k and v together, are passed over, the kernel's output and the
    stream after the first add no longer fit.  The step compiles,
    arguments and program at least 0.1 GiB under the limit by the
    compiler's own peak."""
    lowered, names, sizes = _evabyte_step(one_chip, monkeypatch, 8192)
    assert names == ("eva_ks", "eva_vs", "eva_a", "attn_q", "attn_k",
                     "attn_v", "attn_lse")
    assert 0.8e9 < sum(sizes[n] for n in names) < 0.9e9
    assert sizes["attn_q"] == 4 * 8192 * 4096 * 2
    assert sizes["mlp_up"] == 4 * 8192 * 11008 * 2
    assert all(name in lowered.as_text() for name in F.FULL_KERNEL_NAMES)
    memory = lowered.compile().memory_analysis()
    assert memory.peak_memory_in_bytes <= V5E_BYTES_LIMIT - SPARE
    assert memory.argument_size_in_bytes > 9.8e9


def test_evabyte_step_with_every_name_kept_does_not_fit(one_chip,
                                                        monkeypatch):
    """Why the policy is partial: at 8192 bytes the eleven names stack
    3.1 GB, and the compiler refuses the step for memory."""
    lowered, names, sizes = _evabyte_step(
        one_chip, monkeypatch, 8192, fitting=lambda sizes, room: tuple(sizes))
    assert len(names) == 11 and sum(sizes.values()) > 3.0e9
    with pytest.raises(Exception, match="(?i)ran out of memory|exhausted"):
        lowered.compile()


def test_evabyte_step_at_half_the_length_keeps_more_and_fits(one_chip,
                                                             monkeypatch):
    """The same derivation at 4096 bytes: the stacks are half as large
    beside the same 9.86 GB of state, every name is kept, and the step
    compiles with room to spare."""
    lowered, names, sizes = _evabyte_step(one_chip, monkeypatch, 4096)
    assert names == tuple(sizes) and len(names) == 11
    memory = lowered.compile().memory_analysis()
    assert memory.peak_memory_in_bytes <= V5E_BYTES_LIMIT - SPARE


# ---- the windowed kernels and the step that runs them (PR 33) -------------
# [32 heads, 8192, 128] bfloat16 under a window of 2048 is the call of a
# sliding layer of the benchmark's trinity-mini-5l.train-fit-8k; 1000 is a
# window that is no multiple of the tile or of the grid's block
@pytest.mark.parametrize("window", [2048, 1000])
def test_windowed_flash_kernels_compile_for_v5e(one_chip, window):
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(F.flash_attention(q, k, v, causal=True,
                                         window=window).astype(jnp.float32))
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q)
    text = lowered.as_text()
    assert all(name in text for name in F.WINDOW_KERNEL_NAMES)
    assert not any(name in text for name in F.FULL_KERNEL_NAMES)
    assert _custom_calls(lowered.compile()) == 3


def test_trinity_share_step_compiles_for_v5e_with_room(one_chip,
                                                       monkeypatch):
    """The benchmark's ``trinity-mini-5l`` train step (1 dense + 4 routed
    layers at published widths, 16 of 128 experts, an eighth of the
    vocabulary, bfloat16, ``cache_mode`` none) lowered from shapes for one
    described v5e at one row of 8192 tokens: both kinds of kernel are in
    it, a windowed one for each of the four sliding layers and a full one
    for the fifth, the routed layers' grouped products are the TPU's own
    (no ``[T, E, C]`` one-hot), each routed layer chooses once each way
    between buffers of its pair capacity and the whole-size branch, and
    arguments and program fit the compiler's limit with room: a peak of
    13.53 GiB of 15.75 since the head walks its logits in four chunks (PRs
    36, 37; 13.66 with the logits whole, PR 34; 14.36 when the routed
    buffers held all 65 536 pairs), no higher than with the logits whole,
    and of the head nothing is rematerialized (the whole-array head's
    forward and backward products both were)."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import common
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = common.load_json("configs", "trinity-mini-5l.json")
    traffic = common.load_module("traffic", "moe_lm_fit_stream")
    held = {}

    def built():
        # 705 M parameters and Adam's moments: shapes alone
        net = held["net"] = traffic.build(cfg)
        return net.params, net.state, net.opt_state, net._rng
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(built))
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    before = _head_chunks().get(("8192", "25024", "4"), 0)
    calls_before = _flash_calls()
    lowered = held["net"]._get_jitted("train_step").audit_lower(
        (args + (ids, ids, None, None), {}))
    assert _head_chunks()[("8192", "25024", "4")] == before + 1
    # five attention layers, each handing the kernels k and v whole
    assert {form: n - calls_before[form]
            for form, n in _flash_calls().items()} == {"whole": 5,
                                                       "parts": 0}
    compiled = lowered.compile()
    assert _head_repeats(compiled) == []
    calls = [line for line in compiled.as_text().split("\n")
             if 'custom_call_target="tpu_custom_call"' in line]

    def count(name):
        return sum(f"/{name}/pallas_call" in c for c in calls)
    assert {n: count(n) for n in F.KERNEL_NAMES} == {
        **dict.fromkeys(F.WINDOW_KERNEL_NAMES, 4),
        **dict.fromkeys(F.FULL_KERNEL_NAMES, 1)}
    # three grouped products a routed layer, once more where the backward
    # rebuilds them, and two a product in the backward itself
    assert sum("ragged-dot" in c and "metadata" not in c.split("=")[0]
               for c in calls) >= 4 * 3
    # one conditional a routed layer in the forward, one in the backward
    assert compiled.as_text().count(" conditional(") == 2 * 4
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(8.466e9, rel=1e-3)
    # no higher than with the logits whole (13.661 GiB; compile-only, PR 34)
    assert memory.peak_memory_in_bytes <= 13.661 * 2 ** 30


def test_flash_kernels_compile_at_192_wide_keys_and_128_wide_values(one_chip):
    """Latent attention's call at the benchmark's size: q and k [32, 8192,
    192], v [32, 8192, 128], bfloat16, causal; the forward and both
    backward kernels, unpadded: the output and dV are 128 wide."""
    q = jax.ShapeDtypeStruct((1, 32, 8192, 192), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(F.flash_attention(q, k, v, causal=True).astype(
            jnp.float32))
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, v)
    assert [o.shape[-1] for o in jax.tree_util.tree_leaves(
        lowered.out_info)] == [192, 192, 128]
    text = lowered.as_text()
    assert all(name in text for name in F.FULL_KERNEL_NAMES)
    assert _custom_calls(lowered.compile()) == 3


def _kernel_operands(compiled) -> dict:
    """``{kernel: [(operand's instruction, its shape), ...]}`` of the full
    flash kernels' custom calls in a compiled program, the shapes as the
    call constrains them; the walk's table, the int32 columns a launch
    scalar-prefetches ahead of its operands, left out."""
    found = {}
    for line in compiled.as_text().split("\n"):
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        # the kernel's name ends the call's scope, bare or inside jvp(...)
        name = re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call"', line)
        name = name and name.group(1)
        if name not in F.FULL_KERNEL_NAMES:
            continue
        operands = re.sub(r"/\*index=\d+\*/", "", re.search(
            r" custom-call\(([^)]*)\)", line).group(1))
        shapes = re.findall(r"(\w+\[[\d,]*\])\{", re.search(
            r"operand_layout_constraints=\{(.*?\})\}", line).group(1))
        found.setdefault(name, []).append([
            (o, shape) for o, shape in zip(
                (o.strip().lstrip("%") for o in operands.split(",")), shapes)
            if not re.fullmatch(r"s32\[\d+\]", shape)])
    return found


def _assembled_keys(compiled) -> list:
    """What a compiled program still does to hand the full flash kernels
    their keys: a kernel call that reads a 192-wide key a head (its second
    operand as wide as q), or whose k and v are not one array read twice;
    and every broadcast of a ``[t, 64]`` array to 32 heads."""
    faults = []
    for name, calls in _kernel_operands(compiled).items():
        for (_, q), (k, k_shape), (v, _), *rest in calls:
            shared = rest[0][1] if rest else ""     # the forward has 3 or 4
            if k != v or k_shape == q or not shared.startswith("bf16[1,"):
                faults.append((name, k, k_shape, v, shared))
    faults += [line.split(" = ")[0].strip()
               for line in compiled.as_text().split("\n")
               if re.search(r"= bf16\[1,32,8192,64\]\S* broadcast\(", line)
               and "dimensions={}" not in line]
    return faults


def test_flash_kernels_compile_with_latent_keys_in_parts(one_chip):
    """The same call with the keys as latent attention's projections write
    them: q ``[32, 8192, 192]``, the ``[k_nope | v]`` product ``[32, 8192,
    256]`` read as two 128-wide column blocks, the one rotary key ``[1,
    8192, 64]`` read by all 32 heads; the three kernels compile within
    scoped VMEM, ``dkv`` comes back as the product lies and the shared
    part's gradient as one array."""
    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    q, kv, ks = spec(1, 32, 8192, 192), spec(1, 32, 8192, 256), \
        spec(1, 1, 8192, 64)

    def loss(q, kv, ks):
        return jnp.sum(F.flash_attention(q, kv=kv, k_shared=ks, causal=True)
                       .astype(jnp.float32))
    before = _flash_calls()
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, ks)
    assert _flash_calls() == {**before, "parts": before["parts"] + 1}
    assert [o.shape for o in jax.tree_util.tree_leaves(
        lowered.out_info)] == [q.shape, kv.shape, ks.shape]
    text = lowered.as_text()
    assert all(name in text for name in F.FULL_KERNEL_NAMES)
    compiled = lowered.compile()
    assert _custom_calls(compiled) == 3
    operands = _kernel_operands(compiled)
    assert sorted(operands) == sorted(F.FULL_KERNEL_NAMES)
    for (call,) in operands.values():
        assert [shape for _, shape in call[:4]] == [
            "bf16[32,8192,192]", "bf16[32,8192,256]", "bf16[32,8192,256]",
            "bf16[1,8192,64]"]
    assert "bf16[32,8192,64]" in compiled.as_text()   # the heads' shares


def test_latent_attention_hands_the_kernels_its_keys_as_projected(
        one_chip, monkeypatch):
    """One ``LatentAttention`` at JoyAI's widths, forward and backward at
    ``[1, 8192, 2048]`` bfloat16 (float32 masters cast inside): three
    custom calls, each reading the up-projection's ``[32, 8192, 256]``
    product twice (its two column blocks: no slice, no assembled
    ``[32, 8192, 192]`` key) and the one ``[1, 8192, 64]`` rotary key (no
    broadcast to 32 heads); the dk/dv kernel writes ``[dk_nope | dv]`` as
    the product lies.  The same layer told to assemble (the parts refused)
    shows what the check finds."""
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    from deeplearning4j_tpu.nn.layers.attention import LatentAttention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = LatentAttention(n_in=2048, n_out=2048, n_heads=32, head_dim=128,
                            rope_dim=64, v_dim=128, q_rank=1536, kv_rank=512,
                            rope_theta=32e6, attn_impl="auto")
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32,
                                       sharding=one_chip),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0),
            InputType.recurrent(2048, 8192))["params"]))
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16,
                             sharding=one_chip)

    def loss(p, x):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p)
        return jnp.sum(layer.attend(p, x).astype(jnp.float32))

    def compiled():
        before = _flash_calls()
        c = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, x).compile()
        return c, {form: n - before[form]
                   for form, n in _flash_calls().items()}
    c, calls = compiled()
    assert calls == {"whole": 0, "parts": 1}
    assert _custom_calls(c) == 3
    assert _assembled_keys(c) == []
    # [dk_nope | dv] as the product lies, and the heads' shares of dk_r
    assert re.search(r"flash_bwd_dkv\S* = \(bf16\[32,8192,256\]\S*, "
                     r"bf16\[32,8192,64\]", c.as_text())
    for (call,) in _kernel_operands(c).values():
        # k and v: one array, and a product wrote it
        producer = call[1][0]
        assert call[2][0] == producer and "convolution" in producer
    # the control: with the parts refused the layer assembles, as it did
    monkeypatch.setattr(LatentAttention, "_flash_in_parts",
                        lambda self, t, mask: False)
    c, calls = compiled()
    assert calls == {"whole": 1, "parts": 0}
    assert len(_assembled_keys(c)) >= 3


def test_joyai_share_step_compiles_for_v5e_with_room(one_chip, monkeypatch):
    """The benchmark's ``joyai-llm-flash-5l`` train step (1 dense + 4
    routed layers and the multi-token-prediction module at published
    widths, 16 of 256 experts, an eighth of the vocabulary, bfloat16,
    ``cache_mode`` none) lowered from shapes through the GRAPH container for
    one described v5e at one row of 8193 ids: each full kernel once a
    latent-attention layer, six in all, no windowed one, every call with
    the keys in parts and nothing in the compiled step that assembles or
    broadcasts a key for a kernel (PR 39), the one head over
    both streams walked in four chunks with nothing of it rematerialized,
    and arguments and program fit the compiler's limit with room (a peak of
    13.66 GiB of 15.75, PR 39; 13.70, PR 36; 14.27 with the logits whole,
    PR 35)."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import common
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = common.load_json("configs", "joyai-llm-flash-5l.json")
    traffic = common.load_module("traffic", "mtp_lm_fit_stream")
    held = {}

    def built():
        # 680 M parameters and Adam's moments: shapes alone
        net = held["net"] = traffic.build(cfg)
        return net.params, net.state, net.opt_state, net._rng
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(built))

    def batch(shape, dtype):
        return [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)]
    before = _head_chunks().get(("16384", "16160", "4"), 0)
    calls_before = _flash_calls()
    lowered = held["net"]._get_jitted("train_step").audit_lower(
        (args + (batch((1, 8193), jnp.int32), batch((1, 16384), jnp.int32),
                 None, batch((1, 16384), jnp.float32)), {}))
    assert _head_chunks()[("16384", "16160", "4")] == before + 1
    # six latent layers, each handing the kernels its keys in parts
    assert {form: n - calls_before[form]
            for form, n in _flash_calls().items()} == {"whole": 0,
                                                       "parts": 6}
    compiled = lowered.compile()
    assert _assembled_keys(compiled) == []
    assert _head_repeats(compiled) == []
    calls = [line for line in compiled.as_text().split("\n")
             if 'custom_call_target="tpu_custom_call"' in line]
    assert {n: sum(f"/{n}/pallas_call" in c for c in calls)
            for n in F.KERNEL_NAMES} == {
        **dict.fromkeys(F.FULL_KERNEL_NAMES, 6),
        **dict.fromkeys(F.WINDOW_KERNEL_NAMES, 0)}
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(8.165e9, rel=1e-3)
    assert memory.peak_memory_in_bytes <= V5E_BYTES_LIMIT - 10 * SPARE


# ---- the looped run and the step that walks it (PR 40) --------------------
def test_ouro_step_compiles_with_what_its_looped_run_keeps(one_chip,
                                                           monkeypatch):
    """The benchmark's ``ouro-2p6b-8l`` train step (eight blocks 2048 wide
    walked four times on one set of weights, the final norm inside the
    loop, four exits through the chunked head over 49152 classes,
    bfloat16 under ``cache_mode="remat"``) lowered from shapes for one
    described v5e at one row of 8192 tokens.  The remat rule counts the
    looped run's stacks four times and half as much again for the
    compiler's fragmentation: of the block's names only the log-sum-exp
    (16.8 MB over 32 layer-passes) fits beside the layers' inputs, q
    (1.07 GB) does not.  The scan over the passes is unrolled, so each
    pass's run is a loop of its own: each of the three kernels is in the
    program once in each pass's backward loop and the forward one once
    more in each pass's forward loop; the compiler repeats one product
    where it repeated the blocks' products 22 times under a loop over the
    passes; the head walks 32 chunks; arguments and program fit the
    compiler's limit (14.78 GiB of 15.75, 15.24 under the loop over the
    passes; compile-only, PR 41)."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import common
    from deeplearning4j_tpu.nn import scan_layers
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(scan_layers, "_device_limit",
                        lambda: V5E_BYTES_LIMIT)
    kept = []
    choose = scan_layers.fitting

    def spy(costs, room):
        kept.append((choose(costs, room), dict(costs), room))
        return kept[-1][0]
    monkeypatch.setattr(scan_layers, "fitting", spy)
    cfg = common.load_json("configs", "ouro-2p6b-8l.json")
    traffic = common.load_module("traffic", "loop_lm_fit_stream")
    held = {}

    def built():
        # 612 M parameters and Adam's moments: shapes alone
        net = held["net"] = traffic.build(cfg)
        return net.params, net.state, net.opt_state, net._rng
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(built))
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    before = _head_chunks().get(("32768", "49152", "32"), 0)
    lowered = held["net"]._get_jitted("train_step").audit_lower(
        (args + (ids, ids, None, None), {}))
    assert _head_chunks()[("32768", "49152", "32")] == before + 1
    (names, costs, room), = kept
    assert names == ("attn_lse",)
    # a name's cost: its bytes over 8 layers and 4 passes, half again
    assert costs["attn_q"] == int(1.5 * 32 * 8192 * 2048 * 2)
    assert costs["attn_lse"] == int(1.5 * 32 * 16 * 8192 * 4)
    assert costs["attn_lse"] < room < costs["attn_q"]
    compiled = lowered.compile()
    calls = [line for line in compiled.as_text().split("\n")
             if 'custom_call_target="tpu_custom_call"' in line]
    assert {n: sum(f"/{n}/pallas_call" in c for c in calls)
            for n in F.FULL_KERNEL_NAMES} == {
        "flash_fwd": 8, "flash_bwd_dq": 4, "flash_bwd_dkv": 4}
    # what XLA rematerialized by itself, its tuple reads and bitcasts aside
    repeats = [line for line in _remat_lines(compiled)
               if " bitcast(" not in line
               and " get-tuple-element(" not in line]
    assert len(repeats) <= 2, repeats
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(7.35e9, rel=1e-3)
    assert memory.peak_memory_in_bytes <= 15.0 * 2 ** 30
