"""A configuration names its model kind, and everything the harness knows
of the model comes from ``kinds/<kind>.py``.

* The kind is loaded by path from the harness directory; a configuration
  without a kind, or with one that has no module, is refused (exit 2).
* Through the CNN kind every existing cell's payloads are the images the
  harness drew before kinds existed, bit for bit.
* The seam has a second user: a harness directory whose ``kinds/`` holds a
  token kind (the program's LMAdapter at a small preset, prompts of two
  length buckets) runs end to end on the CPU through ``bench.run``.
"""
import functools
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import _chipbench_path  # noqa: F401
from _chipbench_path import HARNESS
from chipbench import bench, traffic
from chipbench import cell as cell_lib

ROOT = HARNESS.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
KIND_FUNCTIONS = ("init_params", "build_adapter", "payloads", "warm_payloads",
                  "served", "numbers", "control_answers")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_loads_its_kind_from_the_kinds_directory(workload):
    cell = cell_lib.load(workload, ROOT / "BENCHMARK.json")
    assert cell.config["kind"] == "cnn"
    assert os.path.samefile(cell.kind.__file__, HARNESS / "kinds" / "cnn.py")
    for name in KIND_FUNCTIONS:
        assert callable(getattr(cell.kind, name)), name
    assert cell.kind.flops.request_flops is not None


def _checkout(tmp_path, kind):
    """A checkout of the benchmark beside the program, with the f32
    configuration's ``kind`` set to ``kind`` (None: taken out)."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for rel in BENCH["paths"]:
        shutil.copytree(ROOT / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", tmp_path / "src")
    path = tmp_path / "benchmarks" / "chip" / "configs" / "paper-cnn-f32.json"
    config = json.loads(path.read_text())
    if kind is None:
        del config["kind"]
    else:
        config["kind"] = kind
    path.write_text(json.dumps(config))
    return tmp_path


@pytest.mark.parametrize("kind", [None, "", "nosuch", "../chipbench/bench"])
def test_a_configuration_without_a_known_kind_is_refused(kind, tmp_path):
    d = _checkout(tmp_path, kind)
    with pytest.raises(cell_lib.RefusedError, match="kind"):
        cell_lib.load("f32-sessions", d / "BENCHMARK.json",
                      d / "benchmarks" / "chip")
    if kind in (None, "nosuch"):       # the whole run: exit 2, no result
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        proc = subprocess.run(
            [sys.executable] + BENCH["command"][1:]
            + ["--workload", "f32-sessions", "--seed", "5", "--seconds", "1",
               "--trace", "0"],
            cwd=d, env=env, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 2 and proc.stdout.strip() == ""
        assert "kind" in proc.stderr


def _images_drawn_before_kinds(mix, seed, seconds, model):
    """How the harness drew a plan's images before a configuration named
    its kind: N(0, 1) of the input shape from stream 4 of the seed."""
    if mix["loop"] == "open":
        n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    else:
        n = 1024
    shape = tuple(model["in_hw"]) + (model["in_ch"],)
    return np.random.default_rng([int(seed) & ((1 << 64) - 1), 4]
                                 ).standard_normal((n,) + shape,
                                                   dtype=np.float32)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("workload", ["f32-sessions", "fxp16-sessions",
                                      "f32-cold"])
def test_cnn_payloads_are_the_images_drawn_before(workload, seed):
    cell = cell_lib.load(workload, ROOT / "BENCHMARK.json")
    model = cell.config["model"]
    plan = traffic.make_plan(cell.mix, seed, BENCH["run_seconds"],
                             functools.partial(cell.kind.payloads, model))
    want = _images_drawn_before_kinds(cell.mix, seed, BENCH["run_seconds"],
                                      model)
    assert plan.payloads.dtype == want.dtype
    assert plan.payloads.tobytes() == want.tobytes()


# -- a second kind: tokens through the program's LMAdapter ----------------------

TOKEN_MIX = {"generator": "sessions", "loop": "open", "arrivals": "poisson",
             "rate_per_s": 12.0, "predict_first": False,
             "methods": ["token_ixg"], "panel_share": 0.0, "panel_k": 1,
             "lengths": [6, 13]}


def _token_harness(d):
    """A harness directory made of new files only: the token kind, its
    configuration and mix, beside the harness's own readers and peaks."""
    for sub in ("kinds", "configs", "traffic"):
        (d / sub).mkdir()
    shutil.copy(Path(__file__).with_name("_token_kind.py"),
                d / "kinds" / "token.py")
    os.symlink(HARNESS / "metrics", d / "metrics")
    shutil.copy(HARNESS / "peaks.json", d / "peaks.json")
    config = {"name": "tiny-lm", "kind": "token",
              "model": {"arch": "qwen2-1.5b", "size": "smoke"},
              "precision": "f32", "device": "detected", "weight_seed": 11,
              "limits": {"logit_err": 1e-4, "relevance_err": 1e-3}}
    (d / "configs" / "tiny-lm.json").write_text(json.dumps(config))
    (d / "traffic" / "prompts.json").write_text(json.dumps(TOKEN_MIX))
    spec = dict(BENCH)
    spec["configs"] = [{"name": "tiny-lm", "source": "test", "reduced": [],
                        "file": "configs/tiny-lm.json", "why": "test"}]
    spec["workloads"] = [{"name": "tiny-lm", "config": "tiny-lm", "chips": 1,
                          "traffic": "prompts", "why": "test"}]
    (d / "BENCHMARK.json").write_text(json.dumps(spec))
    return d


def test_a_token_kind_runs_end_to_end_through_new_files_alone(tmp_path):
    d = _token_harness(tmp_path)
    prep = bench.prepare("tiny-lm", bench_file=d / "BENCHMARK.json",
                         harness_dir=d, require_accelerator=False)
    plan = prep.plan(2**31 + 77, 1.0)
    groups = prep.kind.warm_payloads(plan, 8)
    assert sorted(g[0].shape[-1] for g in groups) == [8, 16]   # both buckets
    assert all(len({p.shape for p in g}) == 1 and len(g) == 8 for g in groups)

    low = bench.lowerings()
    before = low.count
    result = bench.run("tiny-lm", 2**31 + 77, 1.0, False,
                       t_start=time.monotonic(), bench_file=d / "BENCHMARK.json",
                       harness_dir=d, require_accelerator=False)
    assert low.count == before, low.names[before:]   # nothing lowered inside
    line = json.loads(json.dumps(result))
    assert line["correct"], line["checks"]
    assert line["attempted"] == 12 and line["failed"] == 0
    assert set(line["metrics"]) == {"explain_p50_ms", "setup_s"}
    assert set(line["checks"]) == {"logit_err", "relevance_err",
                                   "failed_requests"}
