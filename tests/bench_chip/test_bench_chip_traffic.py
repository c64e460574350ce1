"""The traffic generator: deterministic per seed, the same timing skeleton
and work in every seed, distinct payloads (the CNN kind's images) across
seeds."""
import collections
import functools

import numpy as np

import _chipbench_path  # noqa: F401
from _chipbench_path import HARNESS
from chipbench import cell as cell_lib
from chipbench import traffic

SESSIONS = {"generator": "sessions", "loop": "open", "arrivals": "poisson",
            "rate_per_s": 50.0, "predict_first": True, "think_mean_s": 0.5,
            "methods": ["saliency", "deconvnet", "guided"],
            "panel_share": 1 / 3, "panel_k": 3}
MODEL = {"in_hw": [32, 32], "in_ch": 3}
CNN = cell_lib.load_kind("cnn", HARNESS)


def _plan(seed, mix=SESSIONS, seconds=20.0):
    return traffic.make_plan(mix, seed, seconds,
                             functools.partial(CNN.payloads, MODEL))


def test_same_seed_same_plan():
    a, b = _plan(2**31 + 12345), _plan(2**31 + 12345)
    np.testing.assert_array_equal(a.arrivals, b.arrivals)
    np.testing.assert_array_equal(a.think, b.think)
    np.testing.assert_array_equal(a.payloads, b.payloads)
    assert a.kinds == b.kinds


def test_seeds_share_the_skeleton_and_differ_in_images():
    a, b = _plan(1), _plan(2**31 + 7)
    np.testing.assert_array_equal(a.arrivals, b.arrivals)
    np.testing.assert_array_equal(a.think, b.think)
    assert a.kinds == b.kinds
    assert not np.array_equal(a.payloads, b.payloads)
    assert a.payloads.shape == b.payloads.shape


def test_open_loop_rate_and_window():
    p = _plan(7)
    assert len(p.arrivals) == 1000
    assert 0 < p.arrivals[0] and p.arrivals[-1] < 20.0
    assert np.all(np.diff(p.arrivals) > 0)
    gaps = np.diff(p.arrivals)
    assert abs(gaps.mean() - 1 / 50) < 0.002
    assert abs(p.think.mean() - 0.5) < 0.02


def test_explain_mix_is_balanced():
    kinds = _plan(3).kinds[:900]
    c = collections.Counter(kinds)
    for m in SESSIONS["methods"]:
        assert c[(m, 3)] == 100 and c[(m, None)] == 200


def test_bursty_keeps_the_mean_rate():
    mix = dict(SESSIONS, arrivals="bursty", burst_factor=8.0,
               burst_len_s=0.05, idle_len_s=0.2)
    p = _plan(5, mix)
    assert len(p.arrivals) == 1000 and p.arrivals[-1] < 20.0
    phase = np.mod(p.arrivals, 0.25)
    on = np.mean(phase < 0.05)
    # 8x for 0.05 s and 0.1x for 0.2 s: 0.4 / 0.42 of arrivals are on
    assert abs(on - 0.4 / 0.42) < 0.03


def test_closed_loop_plan():
    mix = {"generator": "sessions", "loop": "closed", "clients": 192,
           "predict_first": True, "methods": ["saliency", "guided"],
           "panel_share": 0.5, "panel_k": 3}
    p = _plan(9, mix)
    assert p.clients == 192 and len(p.arrivals) == 0
    assert p.think_s(5) == 0.0
    assert set(p.kinds) == {("saliency", 3), ("saliency", None),
                            ("guided", 3), ("guided", None)}
    assert len(p.payloads) == traffic.CLOSED_PAYLOADS


def test_buckets_and_pad_sizes():
    assert traffic.buckets(SESSIONS) == [
        (m, k) for m in SESSIONS["methods"] for k in (None, 3)]
    assert traffic.buckets(dict(SESSIONS, panel_share=0.0)) == [
        (m, None) for m in SESSIONS["methods"]]
    assert traffic.pad_sizes(8) == [1, 2, 4, 8]
    assert traffic.pad_sizes(32) == [1, 2, 4, 8, 16, 32]
    assert traffic.pad_sizes(24) == [1, 2, 4, 8, 16, 24]
