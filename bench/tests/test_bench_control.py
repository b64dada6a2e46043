"""The check that decides ``correct`` sees what it must see, at a size a
test run holds (on the CPU, the engine on its plain-jnp path):

* an honest run passes it;
* the control, the plain reference computed in float8 in the program's
  place, fails the same decision on the same prompts and served tokens;
* a run whose timed path alters the token it produces fails it, and so
  does one whose decode step returns the KV pool unchanged.

Every other part of a run (set-up, warm-up, the window, the reduction)
is the harness's own; only the look for a chip is skipped.
"""
import json
import os
import shutil
import sys

import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]

from bench import harness  # noqa: E402

BENCH = os.path.join(CHECKOUT, "bench")
# at this size, over 17 windows (CPU): an honest run reads at most 7.6e-3
# of a logit, the control at least 2.85e-2, a planted fault ~1; the
# full-size cells hold their own limit
TINY_LIMIT = 0.018


def tiny_cell(root, model="dense_transformer"):
    """Cell "c" of a benchmark root written under ``root``: a two-layer
    model at d 128 under a four-slot backlog. Its model module is the
    dense decoder's, copied there under the name ``model``."""
    for d in ("layer_metrics", "references"):
        shutil.copytree(os.path.join(BENCH, d), root / d)
    shutil.copy(os.path.join(BENCH, "peaks.json"), root / "peaks.json")
    for d in ("configs", "traffic", "models"):
        os.makedirs(root / d)
    shutil.copy(os.path.join(BENCH, "models", "dense_transformer.py"),
                root / "models" / f"{model}.py")
    with open(os.path.join(BENCH, "configs",
                           "starcoder2-3b.gqsa.json")) as f:
        conf = json.load(f)
    conf.update(name="tiny", model=model, num_hidden_layers=2,
                hidden_size=128, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=256,
                vocab_size=512, check={"max_logit_gap": TINY_LIMIT})
    with open(root / "configs" / "tiny.json", "w") as f:
        json.dump(conf, f)
    with open(root / "traffic" / "t.json", "w") as f:
        json.dump({"loop": "backlog", "slots": 4, "queued": 256,
                   "block": 4, "page_size": 16, "max_seq": 128,
                   "sampling": "greedy",
                   "prompt": {"dist": "lognormal", "median": 16,
                              "sigma": 0.5, "min": 8, "max": 32},
                   "output": {"dist": "lognormal", "median": 32,
                              "sigma": 0.5, "min": 16, "max": 64}}, f)
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"] = [{"name": "tiny"}]
    bm["workloads"] = [{"name": "c", "config": "tiny", "traffic": "t",
                        "chips": 1}]
    bm["per_layer"] = [m for m in bm["per_layer"] if m["name"] in (
        "decode_occupancy", "prefill_row_use", "kv_page_use.decode")]
    for m in bm["end_to_end"] + bm["per_layer"]:
        m.pop("workloads", None)
    return harness.load_cell(bm, "c", root=str(root))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    return tiny_cell(root), str(root)


SEED = 2**31 + 3


def _run(cell, root, seed=SEED):
    return harness.run(cell, seed, 1.5, False, require_chip=False,
                       cache_root=os.path.join(root, ".cache"), workers=1,
                       root=root)


def test_honest_run_is_correct_and_the_control_is_not(tiny, monkeypatch):
    cell, root = tiny
    kept = {}
    real = harness.check_outputs

    def keep(cell_, served, seed, model):
        kept.update(served=served, model=model)
        return real(cell_, served, seed, model)
    monkeypatch.setattr(harness, "check_outputs", keep)
    res = _run(cell, root)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    # the window served refills, not only the first fill
    assert any(s.admitted and not s.first_fill and s.produced > 0
               for s in kept["served"])
    assert res["check"]["max_logit_gap"]["value"] <= TINY_LIMIT
    gaps = harness.compare(cell, kept["served"], SEED, control=True)
    control = harness.decide(cell, kept["served"], kept["model"],
                             float(gaps["control_gap"].max()),
                             label="control")
    print(f"honest {float(gaps['gap'].max()):.3e} control "
          f"{control['max_logit_gap']['value']:.3e}")
    assert float(gaps["gap"].max()) <= TINY_LIMIT
    assert control["ok"] is False


def test_a_traced_run_reads_its_window_from_the_trace(tiny):
    cell, root = tiny
    res = harness.run(cell, SEED + 1, 1.5, True, require_chip=False,
                      cache_root=os.path.join(root, ".cache"), workers=1,
                      root=root)
    assert res["correct"], res["check"]
    assert res["device"]["window_s"] >= 1.5
    assert "decode_occupancy" in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_token_altered_where_it_is_produced_is_not_correct(tiny,
                                                             monkeypatch):
    from repro.engine import engine as engine_mod
    cell, root = tiny
    real = engine_mod.sample

    def altered(logits, rng, sp):
        return (real(logits, rng, sp) + 1) % logits.shape[-1]
    engine_mod._step_fns.cache_clear()
    monkeypatch.setattr(engine_mod, "sample", altered)
    try:
        res = _run(cell, root)
    finally:
        engine_mod._step_fns.cache_clear()
    assert not res["correct"]
    assert res["check"]["max_logit_gap"]["value"] > TINY_LIMIT


def test_a_decode_step_that_returns_its_state_unchanged_is_not_correct(
        tiny, monkeypatch):
    import dataclasses
    from repro.engine import engine as engine_mod
    cell, root = tiny
    real = engine_mod.get_model

    def stale(cfg):
        api = real(cfg)

        def decode_step(params, cache, *a, **k):
            logits, _ = api.decode_step(params, cache, *a, **k)
            return logits, cache
        return dataclasses.replace(api, decode_step=decode_step)
    engine_mod._step_fns.cache_clear()
    monkeypatch.setattr(engine_mod, "get_model", stale)
    try:
        res = _run(cell, root)
    finally:
        engine_mod._step_fns.cache_clear()
    assert not res["correct"]
    assert res["check"]["max_logit_gap"]["value"] > TINY_LIMIT
