"""The yardstick's arithmetic: the shape counter, the open-loop generator,
exact percentiles. No device, no program import."""

import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, flops, loadgen  # noqa: E402


def _config(name):
    return common.load_json(os.path.join(ROOT, "benchmark", "configs", f"{name}.json"))


@pytest.mark.parametrize("name,gmacs,convs", [
    ("resnet50_imagenet", 4.09, 53),
    ("resnet18_imagenet", 1.81, 20),
])
def test_forward_macs_match_the_published_counts(name, gmacs, convs):
    cfg = _config(name)
    assert flops.forward_macs(cfg) / 1e9 == pytest.approx(gmacs, rel=0.01)
    ls = flops.layers(cfg)
    assert sum(l["kind"] == "conv" for l in ls) == convs
    assert ls[-1] == {"name": "fc", "kind": "dense",
                      "cin": 512 * cfg["arch"]["expansion"], "cout": 1000}


def test_layer_shapes_follow_the_stages():
    ls = {l["name"]: l for l in flops.layers(_config("resnet50_imagenet"))}
    assert (ls["stem"]["h_out"], ls["stem"]["cout"], ls["stem"]["k"]) == (112, 64, 7)
    assert (ls["s1b1.reduce"]["h_in"], ls["s1b1.reduce"]["cin"]) == (56, 64)
    assert ls["s1b1.proj"]["cout"] == 256 and "s1b2.proj" not in ls
    # the stride sits on the 3x3 of the first block of stages 2-4
    assert (ls["s2b1.mid"]["stride"], ls["s2b1.mid"]["h_out"]) == (2, 28)
    assert (ls["s4b3.expand"]["h_out"], ls["s4b3.expand"]["cout"]) == (7, 2048)


def test_train_flops_leave_out_the_first_data_gradient():
    cfg = _config("resnet18_imagenet")
    ls = flops.layers(cfg)
    fwd = flops.forward_macs(cfg)
    assert flops.train_flops_per_image(cfg) == 2 * (3 * fwd - flops.macs(ls[0]))


def test_conv_roofline_says_which_bound():
    peak = common.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    out = flops.conv_roofline_seconds(_config("resnet50_imagenet"), 256, peak)
    assert out["seconds"] == pytest.approx(out["compute_bound_s"] + out["memory_bound_s"])
    # ResNet-50's 1x1 passes are HBM-bound at bf16, its 3x3 compute-bound
    assert out["memory_bound_s"] > 0 and out["compute_bound_s"] > 0
    one = flops.conv_passes(_config("resnet50_imagenet"), 1)
    assert len(one) == 53 * 3 - 1  # no data gradient for the stem


def test_due_times_are_a_pure_function_of_the_seed():
    a = loadgen.due_times(7, {"rate_rps": 500.0}, 4.0)
    b = loadgen.due_times(7, {"rate_rps": 500.0}, 4.0)
    c = loadgen.due_times(8, {"rate_rps": 500.0}, 4.0)
    assert np.array_equal(a, b) and not np.array_equal(a[:50], c[:50])
    assert np.all(np.diff(a) >= 0) and a[-1] < 4.0
    assert len(a) == pytest.approx(2000, rel=0.1)


def test_bursts_keep_the_mean_rate():
    arr = {"rate_rps": 400.0, "burst": {"period_s": 1.0, "duty": 0.2, "peak_ratio": 4.0}}
    due = loadgen.due_times(3, arr, 10.0)
    assert len(due) == pytest.approx(4000, rel=0.1)
    phase = due % 1.0
    high, low = np.sum(phase < 0.2) / 0.2, np.sum(phase >= 0.2) / 0.8
    assert high / low == pytest.approx(4.0, rel=0.25)


class _Future:
    def __init__(self, delay, fail=False):
        self.t_done, self._fail = None, fail
        self._ev = threading.Event()
        threading.Timer(delay, self._finish).start()

    def _finish(self):
        self.t_done = time.monotonic()
        self._ev.set()

    def result(self, timeout=None):
        if not self._ev.wait(timeout):
            raise TimeoutError
        if self._fail:
            raise RuntimeError("failed")
        return 1


class _Refused(RuntimeError):
    pass


def test_latency_counts_from_due_time_and_lateness_is_reported():
    due = np.array([0.0, 0.01, 0.02, 0.03])
    calls = []

    def submit(x):
        calls.append(x)
        if x == "c":
            raise _Refused()
        if x == "a":
            time.sleep(0.05)  # a slow submit makes the NEXT sends late
        return _Future(0.02, fail=(x == "d"))

    t0, sent = loadgen.run_open_loop(
        submit, ["a", "b", "c", "d"], due, np.arange(4), refused=(_Refused,))
    got = loadgen.collect(sent, timeout_s=5.0)
    assert calls == ["a", "b", "c", "d"]
    assert got["failed"] == 2 and len(got["latency_s"]) == 2
    assert [round(r.due - t0, 6) for r in sent] == [0.0, 0.01, 0.02, 0.03]
    # request b was due at 10 ms, sent ~50 ms (generator held up), done
    # 20 ms later: its latency counts the wait from its DUE time
    assert got["late_s"][-1] >= 0.035
    assert got["latency_s"][-1] >= 0.055
    assert all(r.sent >= r.due for r in sent)


@pytest.mark.parametrize("p,want", [(50, 5), (90, 9), (99, 10), (100, 10), (1, 1)])
def test_percentiles_are_exact_nearest_rank(p, want):
    assert common.percentile(list(range(1, 11)), p) == want


def test_percentile_and_median_edges():
    with pytest.raises(ValueError):
        common.percentile([], 50)
    assert common.median([3, 1, 2]) == 2 and common.median([4, 1, 2, 3]) == 2.5
    assert common.median([]) is None
