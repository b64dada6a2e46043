"""A configuration's model is a module of the benchmark, found by name:

* the dense decoder's module draws, bit for bit, the weights that the
  benchmark drew before it was a module of its own (digests of the
  served tree at a tiny size, taken from the code before the move);
* a linear stacked over a lead shape of two axes (layers, experts) packs
  each slice from its own folded key;
* a configuration whose model module exists only under its own root is
  set up, measured and checked with no edit to any file of ``bench/``.
"""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.dirname(os.path.abspath(__file__)), CHECKOUT,
                os.path.join(CHECKOUT, "src")]

from bench import harness, weights  # noqa: E402
from test_bench_control import tiny_cell  # noqa: E402

BENCH = os.path.join(CHECKOUT, "bench")
TINY = dict(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=256, vocab_size=512)


def _conf(base, **kw):
    with open(harness.find(BENCH, "configs", base)) as f:
        conf = json.load(f)
    conf.update(kw)
    return conf


def _tree_digest(params):
    """sha256 of a tree: its structure (the packed linears' static
    fields with it), then each leaf's path, dtype, shape and bytes."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    h = hashlib.sha256(str(treedef).encode())
    for path, leaf in sorted(leaves,
                             key=lambda pl: jax.tree_util.keystr(pl[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("base,digest", [
    # tanh-GELU, tied head
    ("starcoder2-3b.gqsa",
     "7c263ee26c1cc6994660e91df801a03471216ddc108838ff8f4d888400208e89"),
    # SwiGLU, untied head
    ("mistral-nemo-12b.gqsa",
     "8a42b6b1324a2a258a68772f0c4b1d5dbd092fbae154e8f98d4170f8204a5016"),
])
def test_dense_module_draws_the_pinned_weights(tmp_path, base, digest):
    import jax
    conf = _conf(base, name="tiny", **TINY)
    mod = harness.load_module(harness.find(BENCH, "models", conf["model"]))
    params, parts = mod.build_params(conf, str(tmp_path), jax.devices()[0],
                                     1)
    assert parts["pack_s"] > 0
    assert _tree_digest(params) == digest


def test_a_two_axis_lead_packs_each_slice_from_its_folded_key(tmp_path):
    import jax
    from repro.core.bsr import pack_quantized
    comp = weights.Compression(bits=4, sparsity=0.5, group_size=16)
    seed, lid, n, k = 2**31 + 7, 9, 48, 64
    m = comp.kept(k)
    weights._pack_all([("moe", "w_up", lid, n, k, (2, 3))], comp, seed,
                      str(tmp_path), 1)
    stacked = weights._load_packed(str(tmp_path), jax.devices()[0])
    st = stacked["moe"]["w_up"]["bsr"]
    leaves = [np.asarray(a) for a in jax.tree_util.tree_leaves(st)]
    for layer in range(2):
        for expert in range(3):
            key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(seed), layer), lid), expert)
            want = pack_quantized(*[np.asarray(a) for a in weights.canonical(
                key, n, k, comp.group_size, m)], group_size=comp.group_size)
            got = [a[layer, expert] for a in leaves]
            for g, w in zip(got, jax.tree_util.tree_leaves(want)):
                np.testing.assert_array_equal(g, np.asarray(w))
    # the slices differ: each expert has a key of its own
    assert not np.array_equal(leaves[0][0, 0], leaves[0][0, 1])


def _snapshot(root):
    out = {}
    for dp, dns, fs in os.walk(root):
        dns[:] = [d for d in dns if d not in (".cache", "__pycache__")]
        for f in fs:
            with open(os.path.join(dp, f), "rb") as fh:
                out[os.path.join(dp, f)] = fh.read()
    return out


def test_a_model_module_found_only_under_its_root_runs(tmp_path):
    """A configuration names a model module that exists only under its
    own benchmark root (a copy of the dense decoder's under another
    name); the harness serves it through set-up, the window and the
    check, and no file of ``bench/`` changes."""
    before = _snapshot(BENCH)
    root = tmp_path / "bench"
    cell = tiny_cell(root, model="copied_decoder")
    assert os.listdir(root / "models") == ["copied_decoder.py"]
    assert cell.module.__file__ == str(root / "models" / "copied_decoder.py")
    res = harness.run(cell, 2**31 + 17, 1.0, False, require_chip=False,
                      cache_root=str(root / ".cache"), workers=1,
                      root=str(root))
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    assert _snapshot(BENCH) == before
