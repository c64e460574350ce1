"""The ``jamba`` model kind: AI21-Jamba2-3B's token attribution cell.

* The kind runs end to end through ``bench.run`` on the CPU, at the
  preset's SMOKE size, from its new files alone (the kind, a configuration
  and a mix beside the harness's readers and peaks), and every answer
  agrees with the kind's plain reference.
* Its four per-layer readers read their values from a hand-made trace and
  span list, and read nothing from a program without the token launch
  counts and the scan's names.
* The configuration holds the published config's numbers, the prompt
  lengths come from a stream fixed for every run and the token ids from
  the seed.
"""
import functools
import json
import os
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import _chipbench_path  # noqa: F401
from _chipbench_path import HARNESS
from chipbench import bench, tracing, traffic
from chipbench import cell as cell_lib
from chipbench.context import RunContext

ROOT = HARNESS.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((HARNESS / "configs" / "jamba2-3b-f32.json").read_text())
MIX = json.loads((HARNESS / "traffic" / "prompts-open.json").read_text())
READERS = ("lm.dispatch_mfu", "kernels.ssm_scan_roofline",
           "lm.scan_busy_share", "lm.pad_share")
#: the published config of AI21-Jamba2-3B (the model-configs catalog)
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}
SMOKE_MODEL = {"size": "smoke", "hidden_size": 32, "num_attention_heads": 4,
               "head_dim": 8, "intermediate_size": 64, "vocab_size": 256,
               "mamba_d_state": 4, "mamba_dt_rank": 4}


def _kind():
    return cell_lib.load_kind("jamba")


def _reader(name):
    return cell_lib.metric_reader(name)


def test_the_cell_and_its_configuration_are_the_published_model():
    cell = cell_lib.load("jamba2-3b-prompts", ROOT / "BENCHMARK.json")
    assert cell.chips == 1 and cell.config["kind"] == "jamba"
    for key, value in PUBLISHED.items():
        want = 14 if key == "num_hidden_layers" else value
        assert CONFIG[key] == want, key
    entry, = [c for c in BENCH["configs"] if c["name"] == "jamba2-3b-f32"]
    assert entry["reduced"] == ["num_hidden_layers"]
    model = CONFIG["model"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "intermediate_size", "vocab_size", "mamba_d_state",
                "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
                "attn_layer_period", "attn_layer_offset", "rms_norm_eps"):
        assert model[key] == PUBLISHED[key], key
    assert cell.kind._cfg(model).param_count() == CONFIG["params"]
    names = [m["name"] for m in cell.per_layer]
    assert sorted(names) == sorted(READERS)
    assert {m["name"] for m in cell.end_to_end} == {"explain_p50_ms",
                                                    "setup_s"}


@pytest.mark.parametrize("n", [11, 40])
def test_prompt_lengths_are_fixed_and_token_ids_follow_the_seed(n):
    """Every run of a window's length sends the same lengths, in the
    log-normal's proportions (a window of 11 holds two 4k-token prompts),
    with token ids from its seed."""
    kind = _kind()
    model = CONFIG["model"]
    a = kind.payloads(model, MIX, 2**31 + 5, n)
    b = kind.payloads(model, MIX, 7, n)
    assert [p.size for p in a] == [p.size for p in b]
    lengths = kind.prompt_lengths(MIX, n)
    assert lengths.min() >= 512 and lengths.max() <= 4096
    assert abs(np.median(lengths) - 1024) < 40
    buckets = [kind.bucket(int(s)) for s in lengths]
    assert set(buckets) == {512, 1024, 2048, 4096}
    assert buckets.count(4096) == round(n * 0.161)   # P(length > 2048)
    live = [kind.live_tokens(p) for p in a]
    assert live == list(lengths)
    assert all(not np.array_equal(x, y) for x, y in zip(a, b))
    assert all((p[p.size - s:] > 0).all() and (p[:p.size - s] == 0).all()
               for p, s in zip(a, live))


def test_flops_count_the_published_widths():
    f = _kind().flops
    model = CONFIG["model"]
    d, di, ff = 2560, 5120, 8192
    mamba = d * 2 * di + di * 192 + 160 * di + di * d + 3 * d * ff
    attn = d * 2560 + 2 * d * 128 + 2560 * d + 3 * d * ff
    per_token = 13 * mamba + attn
    t = 1000
    core = 2 * 2 * 20 * 128 * t * (t + 1) / 2
    assert f.explain(model, t) == pytest.approx(
        2 * (2 * t * per_token + 2 * d * 65536) + 3 * core)
    ops, nbytes = f.scan_forward(model, 2, 512)
    assert ops == 2 * 512 * di * (7 * 16 + 1)
    assert nbytes == 4 * (2 * (3 * 512 * di + 2 * 512 * 16 + 2 * di * 16)
                          + di * 16)
    assert f.mamba_layers(model) == 13


def _harness(d: Path) -> Path:
    """A harness directory made of the jamba kind's files alone, at the
    preset's SMOKE size: prompts of 5-16 tokens (buckets 8 and 16)."""
    for sub in ("kinds", "configs", "traffic"):
        (d / sub).mkdir()
    shutil.copy(HARNESS / "kinds" / "jamba.py", d / "kinds" / "jamba.py")
    os.symlink(HARNESS / "metrics", d / "metrics")
    shutil.copy(HARNESS / "peaks.json", d / "peaks.json")
    config = dict(CONFIG, name="jamba-smoke", seats=2,
                  model=dict(CONFIG["model"], **SMOKE_MODEL),
                  limits={"logit_err": 1e-5, "target_gap": 0.0,
                          "relevance_err.p100": 5e-5})
    (d / "configs" / "jamba-smoke.json").write_text(json.dumps(config))
    mix = dict(MIX, rate_per_s=12.0,
               lengths={"distribution": "lognormal", "median": 9,
                        "sigma": 0.3, "min": 5, "max": 16})
    (d / "traffic" / "prompts-smoke.json").write_text(json.dumps(mix))
    spec = dict(BENCH)
    spec["configs"] = [{"name": "jamba-smoke", "source": "test",
                        "reduced": [], "file": "configs/jamba-smoke.json",
                        "why": "test"}]
    spec["workloads"] = [{"name": "jamba-smoke", "config": "jamba-smoke",
                          "chips": 1, "traffic": "prompts-smoke",
                          "why": "test"}]
    (d / "BENCHMARK.json").write_text(json.dumps(spec))
    return d


def test_the_jamba_kind_runs_end_to_end_and_agrees_with_its_reference(
        tmp_path):
    d = _harness(tmp_path)
    seed = 2**31 + 77
    prep = bench.prepare("jamba-smoke", bench_file=d / "BENCHMARK.json",
                         harness_dir=d, require_accelerator=False)
    plan = prep.plan(seed, 1.0)
    groups = prep.kind.warm_payloads(plan, 2)
    assert [g[0].shape[-1] for g in groups] == [8, 16]    # both buckets
    low = bench.lowerings()
    before = low.count
    result = bench.run("jamba-smoke", seed, 1.0, False,
                       t_start=time.monotonic(),
                       bench_file=d / "BENCHMARK.json", harness_dir=d,
                       require_accelerator=False)
    assert low.count == before, low.names[before:]   # nothing lowered inside
    line = json.loads(json.dumps(result))
    assert line["correct"], line["checks"]
    assert line["attempted"] == 12 and line["failed"] == 0
    assert set(line["metrics"]) == {"explain_p50_ms", "setup_s"}
    assert set(line["checks"]) == {"logit_err", "target_gap",
                                   "relevance_err.p100", "failed_requests"}


def test_weights_are_drawn_per_channel_in_the_programs_layout():
    """The kind draws every leaf itself, in the tree the program reads:
    no norm weight, conv bias, ``dt_bias``, ``A`` row or ``D`` is the same
    in every channel."""
    import jax
    import repro.configs as configs
    from repro.models import transformer as tf
    kind = _kind()
    model = dict(CONFIG["model"], **SMOKE_MODEL)
    params = kind.init_params(model, CONFIG["weight_seed"])
    cfg = configs.get_smoke("jamba2-3b")
    want = jax.eval_shape(lambda: tf.init(jax.random.PRNGKey(0), cfg))
    assert (jax.tree.structure(params) == jax.tree.structure(want))
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(want)))
    mixer = params["segments"][0]["mixer"]
    for leaf in (mixer["conv_b"], mixer["dt_bias"], mixer["D"],
                 mixer["dt_norm"]["w"], mixer["b_norm"]["w"],
                 mixer["c_norm"]["w"], params["final_norm"]["w"],
                 params["segments"][1]["norm1"]["w"]):
        v = np.asarray(leaf)
        assert np.unique(v[..., :]).size == v.size
    a = np.exp(np.asarray(mixer["A_log"]))
    assert (a >= 1).all() and (a <= 4).all()
    assert np.unique(a[0], axis=0).shape[0] == a.shape[1]


def test_the_comparison_reads_the_padded_positions():
    """The reference's own answers read as exact; a score at a padded
    position, or a padded prompt explained as if its padding were
    tokens, does not."""
    kind = _kind()
    model = dict(CONFIG["model"], **SMOKE_MODEL)
    params = kind.init_params(model, 5)
    mix = dict(MIX, lengths={"distribution": "lognormal", "median": 9,
                             "sigma": 0.3, "min": 5, "max": 16})
    xs = kind.payloads(model, mix, 2**31 + 3, 4)
    assert any(x.size > kind.live_tokens(x) for x in xs)
    items = [kind.Served(kind="explain", method=m, x=x, logits=None)
             for x, m in zip(xs, ["token_ixg", "token_saliency"] * 2)]
    sound = kind.control_answers(params, model, items, "highest")
    got = kind.numbers(params, model, sound)
    assert got["logit_err"] == 0 and got["relevance_err.p100"] == 0
    leak = [kind.Served(**dict(vars(it))) for it in sound]
    i = next(j for j, it in enumerate(leak)
             if it.x.size > kind.live_tokens(it.x))
    leak[i].relevance = leak[i].relevance.copy()
    leak[i].relevance[0] = np.abs(leak[i].relevance).max()
    assert kind.numbers(params, model, leak)["relevance_err.p100"] >= 1
    unmasked = kind.explain_rows(params, model, [it.x for it in items],
                                 [None] * len(items), "highest")
    wrong = [kind.Served(kind=it.kind, method=it.method, x=it.x,
                         logits=lg, target=t, relevance=sc[it.method])
             for it, (lg, t, sc) in zip(items, unmasked)]
    got = kind.numbers(params, model, wrong)
    assert got["logit_err"] > 1e-3


def _span(name, cat="phase", duration=0.0, **args):
    return SimpleNamespace(name=name, cat=cat, duration=duration, args=args)


def _ev(start_us, dur_us, text):
    return tracing.Event(start_us * 1e3, dur_us * 1e3, text.split(" ")[0],
                         text)


SCAN = ("ssm_scan.{i} long_name=%ssm_scan.{i} = (f32[1,1024,5120]{{2,1,0}}, "
        "f32[1,5120,16]{{2,1,0}}) custom-call(%a, %b), "
        "custom_call_target=\"tpu_custom_call\"")
BWD = ("ssm_scan_bwd.{i} long_name=%ssm_scan_bwd.{i} = (f32[1,1024,5120]"
       "{{2,1,0}}, f32[1,1024,5120]{{2,1,0}}) custom-call(%a, %b), "
       "custom_call_target=\"tpu_custom_call\"")
OTHER = "fusion.{i} tf_op=jit(run)/jvp()/while/body/ffn/dot_general"


def _context(kind, spans, trace):
    model = CONFIG["model"]
    payloads = [np.concatenate([np.zeros(1024 - n, np.int32),
                                np.arange(1, n + 1, dtype=np.int32)])
                for n in (600, 900)]
    window = SimpleNamespace(
        plan=SimpleNamespace(payload=lambda s: payloads[s]),
        records=lambda k=None: [SimpleNamespace(ok=True, session=0),
                                SimpleNamespace(ok=True, session=1),
                                SimpleNamespace(ok=False, session=1)])
    peaks = json.loads((HARNESS / "peaks.json").read_text())
    return RunContext(model=model, precision="f32", chips=1, shards=1,
                      peak=peaks["TPU v5 lite"], window=window, server=None,
                      spans=spans, trace=trace, flops=kind.flops)


def test_the_readers_read_a_hand_made_trace_and_span_list():
    kind = _kind()
    spans = [_span("batch/explain", "batch", 0.5),
             _span("engine.attribute", rows=1, live=1, tokens_live=600,
                   tokens_padded=1024),
             _span("batch/explain", "batch", 0.7),
             _span("engine.attribute", rows=1, live=1, tokens_live=900,
                   tokens_padded=1024)]
    ops, t = [], 0.0
    for i in range(2 * 13 * 2):         # 2 launches, 13 layers, fwd + remat
        ops.append(_ev(t, 100.0, SCAN.format(i=i)))
        t += 100.0
    for i in range(10):
        ops.append(_ev(t, 300.0, BWD.format(i=i)))
        t += 300.0
    for i in range(5):
        ops.append(_ev(t, 400.0, OTHER.format(i=i)))
        t += 400.0
    trace = tracing.DeviceTrace(window_s=1.0, ops={"/device:TPU:0": ops})
    ctx = _context(kind, spans, trace)
    got = {name: _reader(name)(ctx) for name in READERS}

    assert got["lm.pad_share"] == pytest.approx(1 - 1500 / 2048)
    work = (kind.flops.explain(CONFIG["model"], 600)
            + kind.flops.explain(CONFIG["model"], 900))
    assert got["lm.dispatch_mfu"] == pytest.approx(
        100 * work / (1.2 * 197e12))
    ops_, nbytes = kind.flops.scan_forward(CONFIG["model"], 1, 1024)
    need = 2 * 2 * 13 * max(ops_ / 197e12, nbytes / 819e9)
    assert got["kernels.ssm_scan_roofline"] == pytest.approx(
        100 * need / (52 * 100e-6))
    assert got["lm.scan_busy_share"] == pytest.approx(
        (52 * 100 + 10 * 300) / (52 * 100 + 10 * 300 + 5 * 400))


def test_the_readers_read_nothing_from_a_program_without_the_new_spans():
    kind = _kind()
    spans = [_span("batch/explain", "batch", 0.5),
             _span("engine.attribute", rows=1, live=1, seeds=1)]
    trace = tracing.DeviceTrace(
        window_s=1.0, ops={"/device:TPU:0": [_ev(0, 400.0, OTHER.format(i=0)),
                                             _ev(500, 100.0,
                                                 "fusion.7 tf_op=jit(run)/mul")]})
    ctx = _context(kind, spans, trace)
    for name in READERS:
        assert _reader(name)(ctx) is None, name
    ctx.trace = None
    for name in READERS:
        assert _reader(name)(ctx) is None, name


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_warm_shapes_are_every_bucket_the_plan_sends(seed):
    kind = _kind()
    plan = traffic.make_plan(MIX, seed, BENCH["run_seconds"],
                             functools.partial(kind.payloads, CONFIG["model"]))
    groups = kind.warm_payloads(plan, CONFIG["seats"])
    sent = sorted({p.size for p in plan.payloads})
    assert [g[0].size for g in groups] == sent
    assert all(len(g) == CONFIG["seats"] for g in groups)
