"""A run of the chip benchmark without the chip.

* It refuses a CPU backend, a device kind with no published peaks, fewer
  chips than the cell asks for, and a checkout without the program.
* The rest of a run (warm-up, window, drain, comparison with the
  reference) is driven on the CPU at a tiny size with the cell's limits:
  correct on the sound program, and not correct with the timed path broken
  underneath in each way a serving cell can be.
* The control (the reference in the precision below the configuration's,
  in the program's place) fails the limits of each configuration, at the
  published widths.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import _chipbench_path  # noqa: F401
from _chipbench_path import HARNESS
from chipbench import bench, compare, reference
from chipbench import cell as cell_lib

ROOT = HARNESS.parents[1]
CONFIGS = {p.stem: json.loads(p.read_text())
           for p in sorted((HARNESS / "configs").glob("*.json"))}


class _Device:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("devices, chips", [
    ([_Device("cpu", "cpu")], 1),
    ([_Device("tpu", "TPU v9 imaginary")], 1),
    ([_Device("tpu", "TPU v5 lite")], 4),
    ([], 1),
])
def test_refuses_devices_it_cannot_stand_on(devices, chips):
    with pytest.raises(cell_lib.RefusedError):
        cell_lib.check_devices(devices, chips, cell_lib.load_peaks())


def test_accepts_a_v5e():
    got = cell_lib.check_devices([_Device("tpu", "TPU v5 lite")] * 4, 4,
                                 cell_lib.load_peaks())
    assert got == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_main_refuses_the_cpu_backend(capsys):
    import run
    rc = run.main(["--workload", "f32-sessions", "--seed", "3",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "no accelerator" in out.err


def test_refuses_a_checkout_of_the_benchmark_alone(tmp_path):
    """Only BENCHMARK.json and the benchmark's own paths: no program."""
    bench_json = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for rel in bench_json["paths"]:
        shutil.copytree(ROOT / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable] + bench_json["command"][1:]
        + ["--workload", "f32-sessions", "--seed", "5", "--seconds", "1",
           "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


# -- the rest of a run, at a tiny size on the CPU ----------------------------------

TINY_MODEL = {"in_hw": [8, 8], "in_ch": 3, "channels": [4, 8], "kernel": 3,
              "fc": [16], "num_classes": 10, "conv_relu": True,
              "pool_every": 2}
TINY_MIX = {"generator": "sessions", "loop": "open", "arrivals": "poisson",
            "rate_per_s": 30.0, "predict_first": True, "think_mean_s": 0.1,
            "methods": ["saliency", "guided"], "panel_share": 0.5,
            "panel_k": 3}


#: closed-loop sessions on a two-way mesh: a 16-seat launch spans both
#: virtual devices
TINY_CLOSED_MIX = {"generator": "sessions", "loop": "closed", "clients": 24,
                   "predict_first": True, "methods": ["saliency", "guided"],
                   "panel_share": 0.5, "panel_k": 3}


def _prepared(tmp_path_factory, chips, mix, name="tiny"):
    """The f32 configuration's limits and precision on a tiny CNN, on
    ``chips`` devices, as the cell ``name``, prepared and warmed once."""
    d = tmp_path_factory.mktemp("tiny")
    (d / "traffic").mkdir()
    (d / "configs").mkdir()
    os.symlink(HARNESS / "metrics", d / "metrics")
    os.symlink(HARNESS / "kinds", d / "kinds")
    shutil.copy(HARNESS / "peaks.json", d / "peaks.json")
    config = dict(CONFIGS["paper-cnn-f32"], model=TINY_MODEL)
    (d / "configs" / "tiny.json").write_text(json.dumps(config))
    (d / "traffic" / "tiny-mix.json").write_text(json.dumps(mix))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                        "file": "configs/tiny.json", "why": "test"}]
    spec["workloads"] = [{"name": name, "config": "tiny", "chips": chips,
                          "traffic": "tiny-mix", "why": "test"}]
    (d / "BENCHMARK.json").write_text(json.dumps(spec))
    prep = bench.prepare(name, bench_file=d / "BENCHMARK.json",
                         harness_dir=d, require_accelerator=False)
    bench.warm(prep, prep.plan(0, 1.0))
    return prep


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return _prepared(tmp_path_factory, 1, TINY_MIX)


@pytest.fixture(scope="module")
def tiny_mesh(tmp_path_factory):
    prep = _prepared(tmp_path_factory, 2, TINY_CLOSED_MIX)
    assert prep.adapter.n_shards == 2
    return prep


@pytest.fixture(scope="module")
def mesh4_cell():
    """The four-chip cell's real entry: the f32 configuration under the
    closed-loop sessions mix, reporting its rate of explains."""
    cell = cell_lib.load("f32-sessions-mesh4", ROOT / "BENCHMARK.json")
    assert cell.chips == 4 and cell.kind.__name__ == "chipbench_kind_cnn"
    assert cell.config == CONFIGS["paper-cnn-f32"]
    mix = json.loads((HARNESS / "traffic" / "sessions-closed.json")
                     .read_text())
    assert cell.mix == mix and mix["loop"] == "closed"
    assert {m["name"] for m in cell.end_to_end} >= {
        "explains_per_s", "explain_p50_ms", "setup_s"}
    return cell


@pytest.fixture(scope="module")
def tiny_mesh4(tmp_path_factory, mesh4_cell):
    """That cell, on four devices at a tiny CNN: 32-seat launches."""
    prep = _prepared(tmp_path_factory, mesh4_cell.chips, mesh4_cell.mix,
                     name=mesh4_cell.name)
    assert prep.adapter.n_shards == 4
    assert [m["name"] for m in prep.cell.end_to_end] == [
        m["name"] for m in mesh4_cell.end_to_end]
    return prep


def _fault(name, monkeypatch):
    """Break the timed path underneath the harness."""
    from repro.serve import adapters, residual_cache, server
    if name == "answer_altered":          # relevance scaled where produced
        orig = adapters.CNNAdapter.explain_cached

        def explain_cached(self, method, residuals, seeds):
            rel = orig(self, method, residuals, seeds)
            return rel.at[:, 0].multiply(1.01)
        monkeypatch.setattr(adapters.CNNAdapter, "explain_cached",
                            explain_cached)
    elif name == "half_the_batch_left_out":
        orig = adapters.CNNAdapter.explain_cached

        def explain_cached(self, method, residuals, seeds):
            rel = orig(self, method, residuals, seeds)
            return rel.at[:, rel.shape[1] // 2:].set(0.0)
        monkeypatch.setattr(adapters.CNNAdapter, "explain_cached",
                            explain_cached)
    elif name == "logits_altered":
        orig = adapters.CNNAdapter.predict

        def predict(self, xb):
            logits, res = orig(self, xb)
            return logits * 1.001, res
        monkeypatch.setattr(adapters.CNNAdapter, "predict", predict)
    elif name == "state_of_another_request":   # the cache hands back the
        orig = residual_cache.ResidualCache.get  # entry stored before

        def get(self, uid):
            entry = orig(self, uid)
            keys = list(self._entries)
            if entry is not None and len(keys) > 1:
                other = keys[-2] if keys[-1] == uid else keys[-1]
                return self._entries[other]
            return entry
        monkeypatch.setattr(residual_cache.ResidualCache, "get", get)
    elif name == "exchange_between_chips_left_out":
        orig = adapters.CNNAdapter.explain_cached   # rows of every shard but

        def explain_cached(self, method, residuals, seeds):   # the first
            rel = orig(self, method, residuals, seeds)        # never return
            per = -(-rel.shape[1] // self.n_shards)
            return rel.at[:, per:].set(0.0)
        monkeypatch.setattr(adapters.CNNAdapter, "explain_cached",
                            explain_cached)
    elif name == "wrong_target":
        orig = server.ExplanationServer._targets_for

        def targets_for(self, req, logits):
            return (orig(self, req, logits) + 1) % np.shape(logits)[-1]
        monkeypatch.setattr(server.ExplanationServer, "_targets_for",
                            targets_for)
    elif name != "sound":
        raise ValueError(name)


@pytest.mark.parametrize("cell, fault", [
    ("tiny", "sound"), ("tiny", "answer_altered"),
    ("tiny", "half_the_batch_left_out"), ("tiny", "logits_altered"),
    ("tiny", "state_of_another_request"), ("tiny", "wrong_target"),
    ("tiny_mesh", "sound"), ("tiny_mesh", "exchange_between_chips_left_out"),
    ("tiny_mesh4", "sound"),
])
def test_correct_only_when_the_timed_path_is_sound(cell, fault, monkeypatch,
                                                   request):
    prep = request.getfixturevalue(cell)
    _fault(fault, monkeypatch)
    plan = prep.plan(11, 1.0)
    m = bench.measure(prep, plan, 1.0, trace=False)
    if fault == "sound":     # a fault's own eager ops may compile
        assert m.lowered == 0, bench.lowerings().names[-m.lowered:]
    n_explains = len(m.window.records("explain"))
    e2e = bench.end_to_end(prep.cell, m.window, 0.0)
    assert set(e2e) == {x["name"] for x in prep.cell.end_to_end}
    ok, checks, values, n = bench.assess(prep, m, plan)
    assert n > 0 and n_explains > 0
    assert ok == (fault == "sound"), (fault, checks, values)


# -- the control, at the published widths -----------------------------------------

@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_control_fails_the_limits(config, monkeypatch):
    """The reference computed in the precision below the configuration's
    answers every request of a small mix; it must not come out correct."""
    monkeypatch.setattr(reference, "BLOCK", 16)
    cfg = CONFIGS[config]
    model = cfg["model"]
    params = reference.init_params(model, cfg["weight_seed"])
    rng = np.random.default_rng(4)
    x = rng.standard_normal((12,) + tuple(model["in_hw"]) + (model["in_ch"],),
                            dtype=np.float32)
    items = [compare.Served(kind="predict", method=None, x=x[0],
                            logits=np.zeros(10, np.float32))]
    for i, (m, k) in enumerate([(m, k) for m in ("saliency", "deconvnet",
                                                 "guided")
                                for k in (1, 3)] * 2):
        items.append(compare.Served(kind="explain", method=m, x=x[i],
                                    logits=np.zeros(10, np.float32),
                                    targets=tuple(range(k))))
    # the reference itself in the program's place is exact...
    exact = compare.control_answers(params, model, items, "highest")
    ok, checks = compare.judge(compare.numbers(params, model, exact),
                               cfg["limits"])
    assert ok, checks
    # ... and its control is not
    ctrl = compare.control_answers(params, model, items, cfg["control"])
    values = compare.numbers(params, model, ctrl)
    ok, checks = compare.judge(values, cfg["limits"])
    assert not ok, (config, values)
