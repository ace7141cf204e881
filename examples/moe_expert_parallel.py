"""Expert-parallel MoE training over a (data x expert) mesh — beyond the
reference's parallelism classes (SURVEY §2.4 table).

Run on 8 virtual devices:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/moe_expert_parallel.py
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.parallel import init_moe_params, make_moe_train_step


def main():
    n = len(jax.devices())
    dp, ep = 2, n // 2
    embed, hidden = 16, 64
    mesh = Mesh(np.array(jax.devices()[:n]).reshape(dp, ep),
                ("data", "expert"))
    params = init_moe_params(jax.random.PRNGKey(0), n_experts=ep,
                             embed=embed, hidden=hidden)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n * 16, embed)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((embed, embed)), jnp.float32) * 0.5
    y = jnp.tanh(x @ w)
    pspec = {"router": P(None, None), "w1": P("expert"), "w2": P("expert")}
    step = jax.jit(shard_map(
        make_moe_train_step(capacity=32, lr=0.05), mesh=mesh,
        in_specs=(pspec, P(("data", "expert"), None),
                  P(("data", "expert"), None)),
        out_specs=(pspec, P())))
    for i in range(40):
        params, loss = step(params, x, y)
        if i % 10 == 0:
            print(f"step {i}: loss {float(loss):.4f}")
    print(f"final loss {float(loss):.4f} "
          f"({ep} experts sharded over the expert axis, all-to-all dispatch)")


if __name__ == "__main__":
    main()
