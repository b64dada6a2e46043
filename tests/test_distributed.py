"""Distributed correctness on 8 fake host devices.

These run in SUBPROCESSES with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the rest of the suite keeps seeing exactly one CPU device.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_sub(body: str) -> dict:
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys, json
        sys.path.insert(0, {src!r})
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        out = {{}}
    """).format(src=SRC) + textwrap.dedent(body) + \
        "\nprint('RESULT:' + json.dumps(out))\n"
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=560)
    if r.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{r.stderr[-4000:]}")
    for line in r.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise AssertionError(f"no RESULT in output:\n{r.stdout[-2000:]}")


def test_sharded_train_step_matches_single_device():
    out = run_sub("""
        from repro.configs import get_config
        from repro.launch.steps import build_train_step, make_dist
        from repro.models.registry import get_model
        from repro.optim import adamw
        from repro.dist.sharding import param_shardings
        from repro.dist.elastic import plan_mesh, build_mesh

        cfg = get_config("llama2_7b", reduced=True)
        api = get_model(cfg)
        rng = jax.random.PRNGKey(0)
        params = api.init_params(rng, cfg)
        opt = adamw.init_state(params)
        batch = {"tokens": jax.random.randint(rng, (8, 32), 0, cfg.vocab),
                 "labels": jax.random.randint(rng, (8, 32), 0, cfg.vocab)}

        # single device
        dist1 = make_dist(cfg, None)
        step1 = jax.jit(build_train_step(cfg, dist1, adamw.AdamWConfig()))
        p1, o1, m1 = step1(params, opt, batch)

        # 4x2 mesh (DP x TP)
        mesh = build_mesh(plan_mesh(8, model_parallel=2))
        dist = make_dist(cfg, mesh)
        with mesh:
            p_sh = param_shardings(params, dist)
            params_d = jax.device_put(params, p_sh)
            opt_d = adamw.init_state(params_d)
            step = jax.jit(build_train_step(cfg, dist, adamw.AdamWConfig()))
            p2, o2, m2 = step(params_d, opt_d, batch)
        out["loss1"] = float(m1["loss"]); out["loss2"] = float(m2["loss"])
        d = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                               - b.astype(jnp.float32)))),
            p1, p2)
        out["max_param_diff"] = max(jax.tree_util.tree_leaves(d))
    """)
    assert abs(out["loss1"] - out["loss2"]) < 1e-2
    assert out["max_param_diff"] < 5e-2


def test_compressed_psum_error_feedback():
    out = run_sub("""
        from jax import shard_map
        from repro.dist.collectives import compressed_psum_leaf

        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        err0 = jnp.zeros((64,))

        def f(gl, e):
            m, e2 = compressed_psum_leaf(gl[0], e, "data")
            return m[None], e2[None]

        mean_c, err = shard_map(f, mesh=mesh,
                                in_specs=(P("data", None), P(None)),
                                out_specs=(P(None), P("data")),
                                check_vma=False)(g, err0)
        exact = jnp.mean(g, axis=0)
        out["rel_err"] = float(jnp.linalg.norm(mean_c[0] - exact)
                               / jnp.linalg.norm(exact))
        # error feedback: applying again with the carried error reduces bias
        out["err_norm"] = float(jnp.linalg.norm(err))
    """)
    assert out["rel_err"] < 0.05
    assert out["err_norm"] > 0  # feedback is being carried


def test_distributed_decode_attention_matches_dense():
    out = run_sub("""
        from repro.dist.collectives import (sharded_decode_attention,
                                            update_sharded_cache)
        from repro.models.layers import decode_attention

        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
        B, S, KH, D, H = 2, 64, 2, 16, 4
        rng = jax.random.PRNGKey(0)
        q = jax.random.normal(rng, (B, 1, H, D))
        k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, KH, D))
        v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, KH, D))
        length = jnp.int32(40)
        o_dense = decode_attention(q, k, v, length)
        with mesh:
            o_dist = sharded_decode_attention(q, k, v, length, mesh, "data")
        out["max_diff"] = float(jnp.max(jnp.abs(o_dense - o_dist)))

        # sharded cache update writes exactly one position
        cache = jnp.zeros((B, S, KH, D))
        new = jnp.ones((B, 1, KH, D))
        with mesh:
            c2 = update_sharded_cache(cache, new, jnp.int32(17), mesh,
                                      "data")
        out["written"] = float(jnp.sum(c2))
        out["at17"] = float(jnp.sum(c2[:, 17]))
    """)
    assert out["max_diff"] < 1e-4
    assert out["at17"] == out["written"] == 2 * 2 * 16


def test_moe_ep_matches_local():
    out = run_sub("""
        from repro.configs import get_config
        from repro.models import moe as MOE
        from repro.dist.sharding import DistContext
        from repro.dist.elastic import plan_mesh, build_mesh

        import dataclasses
        cfg = get_config("deepseek_moe_16b", reduced=True)
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
        rng = jax.random.PRNGKey(0)
        p = MOE.moe_init(rng, cfg)
        x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 16,
                                                           cfg.d_model)) * .3
        y_local, aux_local = MOE.moe_block(p, x, cfg, None)
        mesh = build_mesh(plan_mesh(8, model_parallel=4))
        dist = DistContext(mesh=mesh, batch_axes=("data",))
        with mesh:
            y_ep, aux_ep = MOE.moe_block(p, x, cfg, dist)
        out["max_diff"] = float(jnp.max(jnp.abs(y_local - y_ep)))
        out["aux_local"] = float(aux_local); out["aux_ep"] = float(aux_ep)
    """)
    # capacity truncation order may differ slightly between 1-device and EP
    assert out["max_diff"] < 0.05
    assert abs(out["aux_local"] - out["aux_ep"]) < 0.2


def test_elastic_restore_onto_smaller_mesh(tmp_path):
    out = run_sub(f"""
        from repro.configs import get_config
        from repro.models.registry import get_model
        from repro.checkpoint.manager import CheckpointManager
        from repro.dist.sharding import DistContext, param_shardings
        from repro.dist.elastic import plan_mesh, build_mesh

        cfg = get_config("llama2_7b", reduced=True)
        api = get_model(cfg)
        params = api.init_params(jax.random.PRNGKey(0), cfg)
        ck = CheckpointManager({str(tmp_path)!r}, async_save=False)

        mesh8 = build_mesh(plan_mesh(8, model_parallel=4))
        dist8 = DistContext(mesh=mesh8, batch_axes=("data",))
        p8 = jax.device_put(params, param_shardings(params, dist8))
        ck.save(1, p8)

        # "lose" half the devices -> restore onto 4-device mesh
        mesh4 = build_mesh(plan_mesh(4, model_parallel=2))
        dist4 = DistContext(mesh=mesh4, batch_axes=("data",))
        p4 = ck.restore(params, shardings=param_shardings(params, dist4))
        d = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
            params, p4)
        out["max_diff"] = max(jax.tree_util.tree_leaves(d))
        out["n_shards"] = len(jax.tree_util.tree_leaves(p4)[1]
                              .sharding.device_set)
    """)
    assert out["max_diff"] == 0.0
