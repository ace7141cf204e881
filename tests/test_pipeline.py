"""Pipeline-parallelism tests: GPipe schedule vs sequential execution,
forward AND gradient parity, plus a combined data×pipe×seq 3D-sharded
transformer training step (the full long-context story on one mesh)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.parallel.pipeline import gpipe, stack_stage_params


def _stage_fn(params, x):
    return jnp.tanh(x @ params["W"] + params["b"])


def _make_stages(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return [{"W": jnp.asarray(rng.standard_normal((d, d)) * 0.3),
             "b": jnp.asarray(rng.standard_normal(d) * 0.1)}
            for _ in range(n)]


def _sequential(stages, xs):
    ys = []
    for i in range(xs.shape[0]):
        h = xs[i]
        for p in stages:
            h = _stage_fn(p, h)
        ys.append(h)
    return jnp.stack(ys)


@pytest.mark.parametrize("n_stages,n_micro", [(4, 4), (4, 8), (8, 8)])
def test_gpipe_matches_sequential(n_stages, n_micro):
    d, mb = 6, 3
    stages = _make_stages(n_stages, d)
    stacked = stack_stage_params(stages)
    xs = jnp.asarray(np.random.default_rng(1)
                     .standard_normal((n_micro, mb, d)))
    mesh = Mesh(np.array(jax.devices()[:n_stages]), ("pipe",))
    fn = shard_map(functools.partial(gpipe, _stage_fn, axis_name="pipe"),
                   mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P())
    out = fn(stacked, xs)
    ref = _sequential(stages, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_gpipe_gradients_match_sequential():
    n_stages, n_micro, d, mb = 4, 4, 5, 2
    stages = _make_stages(n_stages, d, seed=2)
    stacked = stack_stage_params(stages)
    xs = jnp.asarray(np.random.default_rng(3)
                     .standard_normal((n_micro, mb, d)))
    mesh = Mesh(np.array(jax.devices()[:n_stages]), ("pipe",))

    def pipe_loss(stacked, xs):
        ys = gpipe(_stage_fn, stacked, xs, axis_name="pipe")
        return jnp.sum(ys ** 2)

    grad_fn = shard_map(jax.grad(pipe_loss), mesh=mesh,
                        in_specs=(P("pipe"), P()), out_specs=P("pipe"))
    g_pipe = grad_fn(stacked, xs)

    def seq_loss(stacked, xs):
        ys = xs
        for i in range(n_stages):
            ys = _stage_fn(jax.tree.map(lambda p: p[i], stacked), ys)
        return jnp.sum(ys ** 2)

    g_seq = jax.grad(seq_loss)(stacked, xs)
    for k in ("W", "b"):
        np.testing.assert_allclose(np.asarray(g_pipe[k]),
                                   np.asarray(g_seq[k]), atol=1e-6)


def test_gpipe_rejects_too_few_microbatches():
    stages = _make_stages(4, 4)
    stacked = stack_stage_params(stages)
    xs = jnp.zeros((2, 2, 4))
    mesh = Mesh(np.array(jax.devices()[:4]), ("pipe",))
    fn = shard_map(functools.partial(gpipe, _stage_fn, axis_name="pipe"),
                   mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P())
    with pytest.raises(ValueError, match="microbatches"):
        fn(stacked, xs)


def test_3d_transformer_training_step():
    """data=2 × pipe=2 × seq=2 mesh: pipelined transformer blocks with ring
    attention inside, DP gradient reduction — one full sharded train step,
    loss finite and params move.  Model/step shared with the driver dry run
    (``parallel/demo.py``)."""
    from deeplearning4j_tpu.parallel.demo import (build_demo_inputs,
                                                  make_pipelined_train_step)

    stacked, xs, ys = build_demo_inputs(
        n_stages=2, embed=8, n_heads=2, seq_len=8, microbatch=4, n_micro=2,
        seed=7, dtype=jnp.float64)
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("data", "pipe", "seq"))
    train_step = make_pipelined_train_step(n_heads=2)
    fn = shard_map(
        train_step, mesh=mesh,
        in_specs=(P("pipe"), P(None, "data", "seq"), P(None, "data", "seq")),
        out_specs=(P(), P("pipe")))
    loss, new_params = fn(stacked, xs, ys)
    assert np.isfinite(float(loss))
    assert not np.allclose(np.asarray(new_params["Wq"]),
                           np.asarray(stacked["Wq"]))
