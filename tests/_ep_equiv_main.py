"""Subprocess body for test_moe_ep: GSPMD vs shard_map-EP equivalence.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the test
sets it).  Uses a no-drop capacity regime so both dispatch paths are
exact; checks forward outputs and gradients.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.models.common import ModelConfig, axis_rules
from repro.models.ffn import moe_layer, moe_layer_ep


def main() -> None:
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = ModelConfig(
        arch="ep-test", family="moe", n_layers=1, d_model=32, n_heads=4,
        n_kv_heads=2, d_ff=64, vocab=64, n_experts=8, top_k=2,
        capacity_factor=8.0,  # no-drop regime for exact equivalence
        n_shared_experts=1, moe_d_ff=64, dtype=jnp.float32,
    )
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.expert_ff
    params = {
        "router": jax.random.normal(ks[0], (d, e)) * 0.3,
        "wi_gate": jax.random.normal(ks[1], (e, d, f)) * 0.1,
        "wi_up": jax.random.normal(ks[2], (e, d, f)) * 0.1,
        "wo": jax.random.normal(ks[3], (e, f, d)) * 0.1,
        "shared": {
            "wi_gate": jax.random.normal(ks[4], (d, f)) * 0.1,
            "wi_up": jax.random.normal(ks[5], (d, f)) * 0.1,
            "wo": jax.random.normal(ks[6], (f, d)) * 0.1,
        },
    }
    x = jax.random.normal(ks[7], (8, 16, d))

    rules = {"batch": "data", "d_ff": "model", "experts": "data"}

    def f_gspmd(p, x):
        with axis_rules(rules, mesh):
            out, aux = moe_layer(p, x, cfg)
        return out, aux

    def f_ep(p, x):
        with axis_rules(rules, mesh):
            out, aux = moe_layer_ep(p, x, cfg)
        return out, aux

    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    with mesh:
        out_g, aux_g = jax.jit(f_gspmd)(params, xs)
        out_e, aux_e = jax.jit(f_ep)(params, xs)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_e), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_g), float(aux_e), rtol=1e-4)
    print("forward OK")

    def loss_g(p, x):
        out, aux = f_gspmd(p, x)
        return (out.astype(jnp.float32) ** 2).mean() + 0.01 * aux

    def loss_e(p, x):
        out, aux = f_ep(p, x)
        return (out.astype(jnp.float32) ** 2).mean() + 0.01 * aux

    with mesh:
        g_g = jax.jit(jax.grad(loss_g))(params, xs)
        g_e = jax.jit(jax.grad(loss_e))(params, xs)
    for (ka, va), (kb, vb) in zip(
        sorted(jax.tree_util.tree_flatten_with_path(g_g)[0], key=lambda t: str(t[0])),
        sorted(jax.tree_util.tree_flatten_with_path(g_e)[0], key=lambda t: str(t[0])),
    ):
        np.testing.assert_allclose(
            np.asarray(va), np.asarray(vb), rtol=5e-4, atol=5e-4,
            err_msg=f"grad mismatch at {ka}",
        )
    print("grads OK")


if __name__ == "__main__":
    main()
