"""Expert parallelism (``models/moe.py``) on a real multi-device mesh: the
routed experts' stacks sharded over ``model``, each shard computing its
slice's part and the parts summed over the axis, against the uncut layer
on one device, alone and inside the launch path's train steps. Needs more
than one device, so runs in a subprocess with
--xla_force_host_platform_device_count=4."""
import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.launch import sharding as shard_rules
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import (TrainPolicy, make_init_fn,
                                    make_train_step, state_shardings)
    from repro.models import moe

    # 20 routed experts padded to 32: 8 a shard over 4, the last shard's
    # 4 real experts beside 4 of padding
    cfg = dataclasses.replace(get_config("qwen2-moe-a2.7b").reduced(),
                              n_experts=20, moe_top_k=4)
    assert moe.expert_stack_size(cfg) == 32
    held = []
    routed = moe.routed_experts

    def recording(p, xf, cfg, first_expert=0):
        held.append(p["w_gate"].shape[0])
        return routed(p, xf, cfg, first_expert)
    moe.routed_experts = recording

    # float32 sums of the same products, split over the shards
    tol = dict(rtol=1e-5, atol=1e-5)

    # one layer: out and gradients against the uncut layer
    p = moe.init_moe_block(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    r = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def f(p, x):
        out, aux = moe.moe_forward(p, x, cfg)
        return jnp.sum(out * r) + aux, out
    grad = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)
    (l0, o0), g0 = jax.jit(grad)(p, x)
    assert held == [32], held
    mesh = make_local_mesh(1, 4)
    moe.set_expert_parallel_mesh(mesh)
    sh = shard_rules.param_shardings(cfg, {"blocks": {"mlp": p}},
                                     mesh)["blocks"]["mlp"]
    assert sh["w_gate"].spec[0] == "model"
    (l1, o1), g1 = jax.jit(grad)(jax.device_put(p, sh), x)
    assert held[1:] == [8], held
    np.testing.assert_allclose(o1, o0, **tol)
    np.testing.assert_allclose(l1, l0, **tol)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(a, b, **tol)

    # the launch path's train steps: experts over 2 model shards against
    # one, both with 2 data shards (a manual data axis of size 1 around
    # the expert shard_map fails XLA's partitioner, with or without this
    # layer: a mesh the launch path does not run)
    toks = jax.random.randint(jax.random.PRNGKey(3), (4, 32), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=-1)}
    for mode in ("pssgd", "localsgd"):
        runs = []
        for shape in ((2, 2), (2, 1)):
            mesh = make_local_mesh(*shape)
            moe.set_expert_parallel_mesh(mesh)
            policy = TrainPolicy(mode=mode, lr=1e-2, local_steps=1)
            held.clear()
            with mesh:
                init = make_init_fn(cfg, policy, mesh)
                sds = jax.eval_shape(init, jax.random.PRNGKey(0))
                state = jax.jit(init, out_shardings=state_shardings(
                    cfg, policy, mesh, sds))(jax.random.PRNGKey(0))
                new, m = jax.jit(make_train_step(cfg, policy, mesh))(
                    state, batch)
            assert set(held) == {32 // shape[1]}, (shape, held)
            runs.append((m["loss"], new["params"]))
        (l_ep, p_ep), (l_one, p_one) = runs
        np.testing.assert_allclose(l_ep, l_one, **tol)
        for a, b in zip(jax.tree.leaves(p_ep), jax.tree.leaves(p_one)):
            np.testing.assert_allclose(a, b, **tol)
    print("EP_OK")
""")


def test_expert_parallel_layer_and_train_steps_match_the_uncut_layer():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, env=env, timeout=900)
    assert "EP_OK" in r.stdout, r.stdout[-3000:] + r.stderr[-5000:]
