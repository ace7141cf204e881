"""Multi-chip dry run used by the driver (``__graft_entry__.dryrun_multichip``).

Builds an n-device mesh, shards the FULL training step (forward+backward+
optimizer update) with real dp×tp shardings, and executes one step on tiny
shapes.  Upgraded alongside the flagship model.
"""
from __future__ import annotations

import os

import numpy as np


def provision_devices(n_devices: int):
    """Return >= n_devices jax devices, self-provisioning a virtual CPU mesh.

    Real-hardware path first: if the default backend already exposes enough
    devices (an actual pod slice), use them.  Otherwise force the host
    platform to expose ``n_devices`` virtual CPU devices.  XLA_FLAGS must be
    set before the CPU backend initializes — it is lazy per-platform, so this
    works even when a TPU backend is already up: ``jax.devices()`` still
    reports the TPU, but ``jax.devices('cpu')`` honors the flag.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags +
            f" --xla_force_host_platform_device_count={max(n_devices, 8)}"
        ).strip()

    import jax

    try:
        devices = jax.devices()
    except RuntimeError:
        devices = []  # default backend failed to init
    if len(devices) >= n_devices:
        return devices[:n_devices]
    try:
        cpu = jax.devices("cpu")
    except RuntimeError:
        cpu = []
    if len(cpu) >= n_devices:
        return cpu[:n_devices]
    return None  # backend already up with too few devices; caller re-execs


def _run_in_subprocess(n_devices: int) -> None:
    """Re-exec the dry run in a fresh interpreter where XLA_FLAGS and
    JAX_PLATFORMS are set BEFORE jax initializes — the only reliable route
    when the calling process already brought up a too-small backend."""
    import subprocess
    import sys

    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={max(n_devices, 8)}"
    ).strip()
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c",
         "from deeplearning4j_tpu.parallel import dryrun; "
         f"dryrun._child_main({n_devices})"],
        env=env, capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(
            f"subprocess dryrun failed (rc={proc.returncode}):\n"
            + proc.stderr[-4000:])


def run(n_devices: int) -> None:
    """Hermetic entry point: the dry run is a CPU-mesh *correctness* check and
    must never fail because of default-backend (TPU) health.  The in-process
    path pins every uncommitted array to the mesh devices; if it still fails
    for any reason (e.g. a TPU client that fails backend init), fall back
    to a fresh ``JAX_PLATFORMS=cpu`` subprocess, which cannot see the TPU at
    all.  Mirrors the reference's always-runnable local-cluster proof
    (dl4j-spark BaseSparkTest.java:46 — ``local[N]``, no real cluster)."""
    devices = provision_devices(n_devices)
    if devices is None:
        return _run_in_subprocess(n_devices)
    try:
        _run_in_process(n_devices, devices)
    except Exception as e:
        import sys
        # stderr, not warnings.warn: the fallback must survive
        # warnings-as-errors runs.  If the subprocess also fails, Python's
        # implicit __context__ chaining preserves this first traceback.
        print(f"in-process dryrun failed ({type(e).__name__}: {e}); "
              "falling back to hermetic JAX_PLATFORMS=cpu subprocess",
              file=sys.stderr)
        _run_in_subprocess(n_devices)


def _child_main(n_devices: int) -> None:
    """Entry point the hermetic subprocess runs.  Never re-spawns — a failure
    here is terminal (surfaced to the parent via the subprocess rc), so the
    fallback chain is bounded at one level by construction."""
    devices = provision_devices(n_devices)
    if devices is None:
        raise RuntimeError(
            f"hermetic child could not provision {n_devices} devices")
    _run_in_process(n_devices, devices)


def _run_in_process(n_devices: int, devices) -> None:
    import jax

    # Pin uncommitted array creation (model init, PRNG keys, demo inputs) to
    # the dry-run devices.  Without this, when the default backend is a lone
    # TPU and the mesh is the CPU fallback, init ops run on the TPU and any
    # TPU-side flake fails a check whose purpose is CPU-mesh correctness.
    with jax.default_device(devices[0]):
        _train_steps(n_devices, devices)


def _train_steps(n_devices: int, devices) -> None:
    import jax

    from ..nn.conf.input_type import InputType
    from ..nn.conf.multi_layer import NeuralNetConfiguration
    from ..nn.conf.updaters import Adam
    from ..nn.layers.feedforward import DenseLayer, OutputLayer
    from ..nn.multilayer import MultiLayerNetwork
    from .mesh import make_mesh
    from .wrapper import ParallelWrapper, megatron_dense_rule

    tp = 2 if n_devices % 2 == 0 else 1
    mesh = make_mesh(n_devices, tp=tp, devices=devices)

    conf = (NeuralNetConfiguration.builder()
            .seed(42).activation("relu").weight_init("xavier")
            .updater(Adam(learning_rate=1e-3))
            .list()
            .layer(DenseLayer(n_out=64))
            .layer(DenseLayer(n_out=64))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(784))
            .build())
    model = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    dp = n_devices // tp
    batch = dp * 8  # divisible by the data axis (sharding requires it)
    x = rng.standard_normal((batch, 784), dtype=np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]

    pw = ParallelWrapper(model, mesh, param_rule=megatron_dense_rule(model.params))
    # The PRNG key was created on the default backend at init; commit it to
    # the dry-run devices so the jitted step doesn't see mixed placements
    # (relevant when the default backend is a lone TPU and the mesh is CPU).
    model._rng = jax.device_put(model._rng, devices[0])
    pw.fit(x, y)
    assert np.isfinite(model.get_score()), "dry-run step produced non-finite loss"

    if n_devices % 8 == 0:
        _pipeline_seq_step(n_devices, devices)
        _expert_parallel_step(n_devices, devices)


def _pipeline_seq_step(n_devices: int, devices) -> None:
    """data×pipe×seq 3D-sharded transformer train step: GPipe microbatching
    with ring attention inside each stage, DP gradient pmean, SGD update.
    Model + step come from ``demo.py`` (shared with the pipeline tests)."""
    import jax
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from .demo import build_demo_inputs, make_pipelined_train_step

    dp, pp, sp = 2, 2, n_devices // 4
    stacked, xs, ys = build_demo_inputs(
        n_stages=pp, embed=8, n_heads=2, seq_len=4 * sp, microbatch=2 * dp,
        n_micro=pp)
    mesh = Mesh(np.array(devices[:n_devices]).reshape(dp, pp, sp),
                ("data", "pipe", "seq"))
    train_step = make_pipelined_train_step(n_heads=2)
    in_specs = (P("pipe"), P(None, "data", "seq"), P(None, "data", "seq"))
    # Inputs were built on the default backend; commit them to the mesh
    # (cross-backend device_put) so the jitted program sees one placement.
    stacked = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P("pipe"))), stacked)
    xs = jax.device_put(xs, NamedSharding(mesh, in_specs[1]))
    ys = jax.device_put(ys, NamedSharding(mesh, in_specs[2]))
    fn = jax.jit(shard_map(  # graftlint: disable=JX028  (dry-run validation probe; compiled once, never dispatched steady-state)
        train_step, mesh=mesh, in_specs=in_specs,
        out_specs=(P(), P("pipe"))))
    loss, _ = fn(stacked, xs, ys)
    assert np.isfinite(float(loss)), "pipeline dry-run produced non-finite loss"


def _expert_parallel_step(n_devices: int, devices) -> None:
    """data×expert MoE train step: top-1 routed FFN, tiled all-to-all
    token exchange over the expert axis, DP grad reduction."""
    import jax
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from .expert import init_moe_params, make_moe_train_step

    dp, ep = 2, n_devices // 2
    embed, hidden, experts = 8, 16, ep
    mesh = Mesh(np.array(devices[:n_devices]).reshape(dp, ep),
                ("data", "expert"))
    params = init_moe_params(jax.random.PRNGKey(0), experts, embed, hidden)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n_devices * 4, embed)).astype(np.float32)
    y = np.tanh(x @ rng.standard_normal((embed, embed)).astype(np.float32))
    pspec = {"router": P(None, None), "w1": P("expert"), "w2": P("expert")}
    batch_spec = P(("data", "expert"), None)
    params = {k: jax.device_put(v, NamedSharding(mesh, pspec[k]))
              for k, v in params.items()}
    x = jax.device_put(x, NamedSharding(mesh, batch_spec))
    y = jax.device_put(y, NamedSharding(mesh, batch_spec))
    fn = jax.jit(shard_map(  # graftlint: disable=JX028  (dry-run validation probe; compiled once, never dispatched steady-state)
        make_moe_train_step(capacity=4), mesh=mesh,
        in_specs=(pspec, batch_spec, batch_spec),
        out_specs=(pspec, P())))
    _, loss = fn(params, x, y)
    assert np.isfinite(float(loss)), "MoE dry-run produced non-finite loss"
