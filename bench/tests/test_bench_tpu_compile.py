"""Both benchmark configurations' decode step and largest prefill bucket
(the first fill's) compile for one TPU v5e chip at the cells' slot counts, and fit its
memory by the compiler's count.

Nothing runs: the engine's jitted steps are lowered with the Pallas
kernels on and compiled for a chip that is described, not attached. The
topology is described inside a fixture, never at import, so only the
worker that runs this file loads the TPU library.
"""
import json
import os
import sys

import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]

HBM_BYTES = 16 * 2**30

CELLS = [("starcoder2-3b.gqsa", "decode_backlog"),
         ("mistral-nemo-12b.gqsa", "decode_backlog")]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _load(config, traffic):
    from bench.harness import find
    with open(find(os.path.join(CHECKOUT, "bench"), "configs",
                   config)) as f:
        conf = json.load(f)
    with open(find(os.path.join(CHECKOUT, "bench"), "traffic",
                   traffic)) as f:
        tr = json.load(f)
    return conf, tr


def _param_shapes(conf, sharding):
    """ShapeDtypeStructs of the served tree, as bench/weights.py builds
    it: every packed linear stacked over layers, bf16 unpacked leaves."""
    import jax
    import jax.numpy as jnp
    from repro.core.gqs_layer import GQSAConfig, packed_linear_shapes
    from repro.core.pruning import PruneConfig
    from repro.core.quant import QuantConfig
    from bench.models.dense_transformer import Model
    from bench.weights import Compression
    m, c = Model.from_conf(conf), Compression.from_conf(conf["compression"])
    gq = GQSAConfig(quant=QuantConfig(bits=4, group_size=c.group_size),
                    prune=PruneConfig(sparsity=c.sparsity,
                                      group_size=c.group_size))
    dt = jnp.dtype(conf["leaf_dtype"])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def stacked(n, k):
        base = packed_linear_shapes(n, k, gq)["bsr"]
        leaves, tdef = jax.tree_util.tree_flatten(base)
        return {"bsr": tdef.unflatten([sds((m.layers,) + l.shape, l.dtype)
                                       for l in leaves])}
    layers = {"ln1": sds((m.layers, m.d), dt), "ln2": sds((m.layers, m.d), dt)}
    for block, name, n, k in m.linears():
        layers.setdefault(block, {})[name] = stacked(n, k)
    params = {"embed": sds((m.vocab, m.d), dt), "layers": layers,
              "final_norm": sds((m.d,), dt)}
    if not m.tied:
        params["lm_head"] = {"w": sds((m.vocab, m.d), dt)}
    return params


@pytest.mark.parametrize("config,traffic", CELLS)
def test_steps_compile_and_fit(one_chip, monkeypatch, config, traffic):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.engine import SamplingParams
    from repro.engine.engine import _step_fns
    from repro.kernels import ops
    from repro.models.registry import get_model
    from bench.harness import engine_config, shapes
    from bench.models.dense_transformer import program_config
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    conf, tr = _load(config, traffic)
    cfg, ecfg = program_config(conf), engine_config(tr)
    b = ecfg.num_slots
    mp = -(-ecfg.max_seq // ecfg.page_size)
    cache = jax.eval_shape(lambda: get_model(cfg).init_paged_cache(
        cfg, b * mp, ecfg.page_size))
    cache = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        cache)
    params = _param_shapes(conf, one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    _step_fns.cache_clear()
    try:
        prefill, decode, _ = _step_fns(cfg, SamplingParams(), True)
        sh = shapes(tr, ecfg)
        dec = decode.lower(params, cache, i32(b), i32(b), i32(b, mp),
                           i32(b), rng, max(sh["decode"])).compile()
        pre = prefill.lower(params, cache, i32(b, sh["first_fill"][0]),
                            i32(b), i32(b, mp), rng).compile()
    finally:
        _step_fns.cache_clear()
    for what, comp in (("decode", dec), ("prefill", pre)):
        ma = comp.memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        print(f"{config} x {traffic} {what}: arguments "
              f"{ma.argument_size_in_bytes / 2**30:.2f} GiB, outputs "
              f"{ma.output_size_in_bytes / 2**30:.2f} GiB, temp "
              f"{ma.temp_size_in_bytes / 2**30:.2f} GiB, total "
              f"{total / 2**30:.2f} of {HBM_BYTES / 2**30:.0f} GiB")
        assert "tpu_custom_call" in comp.as_text()
        assert total < HBM_BYTES
    assert np.all(np.asarray(sh["decode"]) <= mp)
