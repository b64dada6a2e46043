"""The harness finds each configuration, traffic mix and per-layer metric
by name, takes up a new one that a test writes beside the others without
an edit to any existing file, refuses unknown names, and refuses to run
without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]

from bench import harness  # noqa: E402

BENCH = os.path.join(CHECKOUT, "bench")


def _benchmark():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def _copy_bench(tmp_path):
    root = tmp_path / "bench"
    for d in ("configs", "traffic", "layer_metrics", "models", "references"):
        shutil.copytree(os.path.join(BENCH, d), root / d)
    shutil.copy(os.path.join(BENCH, "peaks.json"), root / "peaks.json")
    return str(root)


def test_every_cell_of_the_benchmark_loads():
    bm = _benchmark()
    for w in bm["workloads"]:
        cell = harness.load_cell(bm, w["name"])
        assert cell.conf["name"] == w["config"]
        assert cell.traffic["sampling"] == "greedy"
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert set(cell.readers) == {m["name"] for m in cell.per_layer}


def test_new_files_are_found_by_name_without_edits(tmp_path):
    root = _copy_bench(tmp_path)
    before = {os.path.join(dp, p): open(os.path.join(dp, p)).read()
              for dp, _, fs in os.walk(root) for p in fs}
    with open(os.path.join(root, "configs",
                           "starcoder2-3b.gqsa.json")) as f:
        conf = json.load(f)
    conf.update(name="tiny.gqsa", num_hidden_layers=1)
    with open(os.path.join(root, "configs", "tiny.gqsa.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(root, "traffic", "burst.json"), "w") as f:
        json.dump({"loop": "backlog", "slots": 2, "queued": 6, "block": 3,
                   "page_size": 16, "max_seq": 64, "sampling": "greedy",
                   "prompt": {"dist": "lognormal", "median": 8,
                              "sigma": 0.5, "min": 4, "max": 16},
                   "output": {"dist": "lognormal", "median": 8,
                              "sigma": 0.5, "min": 4, "max": 16}}, f)
    with open(os.path.join(root, "layer_metrics", "slots_seen.py"),
              "w") as f:
        f.write("def read(ctx):\n    return float(ctx.slots)\n")
    bm = _benchmark()
    bm["configs"].append({"name": "tiny.gqsa"})
    bm["workloads"].append({"name": "tiny.burst", "config": "tiny.gqsa",
                            "traffic": "burst", "chips": 1})
    bm["per_layer"].append({"name": "slots_seen", "unit": "1",
                            "workloads": ["tiny.burst"]})
    cell = harness.load_cell(bm, "tiny.burst", root=root)
    assert cell.conf["num_hidden_layers"] == 1
    assert cell.traffic["queued"] == 6
    assert list(cell.readers) == ["slots_seen"]
    assert cell.readers["slots_seen"](harness.Context(slots=2)) == 2.0
    for path, text in before.items():
        assert open(path).read() == text


@pytest.mark.parametrize("kind,name", [("configs", "no-such-model"),
                                       ("traffic", "no_such_mix"),
                                       ("layer_metrics", "no_such_metric"),
                                       ("models", "no_such_module")])
def test_unknown_names_are_refused(kind, name):
    with pytest.raises(KeyError, match=name):
        harness.find(BENCH, kind, name)


@pytest.mark.parametrize("model", [None, "no_such_module"])
def test_a_config_without_a_known_model_module_is_refused(tmp_path, model):
    """No default: a configuration that names no model module, or one
    that is not there, is refused with the modules there are."""
    root = _copy_bench(tmp_path)
    path = os.path.join(root, "configs", "starcoder2-3b.gqsa.json")
    with open(path) as f:
        conf = json.load(f)
    conf.pop("model")
    if model is not None:
        conf["model"] = model
    with open(path, "w") as f:
        json.dump(conf, f)
    with pytest.raises(KeyError, match=r"model.*have: dense_transformer"):
        harness.load_cell(_benchmark(), "sc2-3b.decode_backlog", root=root)


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        harness.load_cell(_benchmark(), "no.such.cell")


def test_unknown_device_kind_has_no_peaks():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks_for("cpu")


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "sc2-3b.decode_backlog", "--seed", str(2**31 + 11), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run_py(CHECKOUT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_exits_nonzero_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    p = _run_py(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _backlog_shapes():
    from repro.engine import EngineConfig
    with open(os.path.join(BENCH, "traffic", "decode_backlog.json")) as f:
        tr = json.load(f)
    ecfg = EngineConfig(num_slots=32, max_seq=768, page_size=16)
    return harness.shapes(tr, ecfg), ecfg


def test_shapes_cover_the_traffic():
    sh, _ = _backlog_shapes()
    # queued prompts 38..241 pad to 64, 128 or 256 rows
    assert sh["prefill"] == [64, 128, 256]
    # every request reserves at least 38 + 240 tokens (18 pages): a live
    # batch clamps to 32 pages or to the cap, the 48 pages of max_seq
    assert sh["decode"] == [32, 48]
    # the first fill's longest prompt (its own and the tokens it stands
    # for) pads to max_seq
    assert sh["first_fill"] == [768]


def test_warm_groups_reach_every_shape_with_the_state_committed():
    sh, ecfg = _backlog_shapes()
    groups = harness.warm_groups(sh, ecfg)
    pages = [[-(-(len(r.prompt) + r.max_new) // 16) for r in g]
             for g in groups]
    buckets = [harness._bucket(len(max(g, key=lambda r: len(r.prompt))
                                   .prompt), 8) for g in groups]
    # the first group holds 32 pages; a later one holds the cap
    assert pages[0][0] == 32 and max(p[0] for p in pages) == 48
    # every prefill bucket runs after the first prefill (which meets the
    # engine's initial state), each short request alone or beside a holder
    assert sorted(set(buckets[1:])) == sh["prefill"]
    assert buckets[0] == sh["prefill"][0]
    for g in groups:
        assert all(len(r.prompt) + r.max_new <= ecfg.max_seq for r in g)
        assert sum(r.max_new <= 2 for r in g) == 1
