"""The trace reduction: interval arithmetic on hand-made intervals, and
the xplane reader on one small recorded TPU trace (one ResNet-18 train
step on a v5e, recorded in PR 22). No topology or device call at import."""

import gzip
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "r18_train_one_step.xplane.pb.gz")


def test_union_merges_overlapping_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [(0, 4), (5, 7)]
    assert tr.total(tr.union([(0, 2), (1, 3)])) == 3


def test_union_keeps_a_contained_interval_inside():
    assert tr.union([(0, 10), (2, 3), (4, 12)]) == [(0, 12)]


def test_gaps_are_what_the_busy_union_leaves_open():
    busy = tr.union([(2, 4), (6, 7)])
    assert tr.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps(busy, 3, 6.5) == [(4, 6)]
    assert tr.gaps([], 1, 2) == [(1, 2)]


def test_subtract_and_intersect():
    a, b = [(0, 10)], [(2, 3), (5, 7)]
    assert tr.subtract(a, b) == [(0, 2), (3, 5), (7, 10)]
    assert tr.intersect(a, b) == [(2, 3), (5, 7)]
    assert tr.intersect([(0, 4), (6, 9)], [(3, 7)]) == [(3, 4), (6, 7)]


def test_gap_attribution_to_the_host_span_open_at_the_time():
    idle = [(0, 10), (20, 24)]
    spans = {"zoo.readback": [(2, 6)], "zoo.dispatch": [(4, 9), (21, 22)],
             "zoo.data": [(100, 101)]}
    got = tr.attribute(idle, spans)
    # readback comes first in the order, so it keeps 4..6 where both are open
    assert got == {"zoo.readback": 4, "zoo.dispatch": 3 + 1, "unattributed": 3 + 3}
    assert sum(got.values()) == tr.total(idle)


def test_attribution_without_spans_is_unattributed():
    assert tr.attribute([(0, 5)], {}) == {"unattributed": 5}


def test_exposed_collective_time_is_what_compute_does_not_cover():
    collective = [(0, 4), (10, 12)]
    compute = [(1, 2), (3, 11)]
    assert tr.exposed(collective, compute) == 1 + 1 + 1
    assert tr.exposed(collective, []) == 6
    assert tr.exposed(collective, [(0, 20)]) == 0


@pytest.mark.parametrize("text,want", [
    ("%fusion.15 = bf16[256,112,112,64]{0,3,2,1:T(8,128)(2,1)} fusion(bf16[256,112,112,64]{0,3,2,1} %x, bf16[64]{0} %y), kind=kLoop, calls=%fused_computation.20",
     ("fusion.15", "other")),
    ("%copy_add_fusion = (f32[7,7,3,64]{3,2,1,0}, f32[7,7,3,64]{3,2,1,0}) fusion(f32[7,7,3,64]{3,1,2,0} %a, bf16[256,224,224,3]{0,2,3,1} %b), kind=kOutput, calls=%fused_computation.530",
     ("copy_add_fusion", "conv")),
    ("%convolution_add_fusion = bf16[256,56,56,64]{0,3,2,1} fusion(bf16[256,56,56,64]{0,3,2,1} %p), kind=kOutput, calls=%fc.3",
     ("convolution_add_fusion", "conv")),
    ("%convolution.5 = bf16[8,7,7,512]{3,2,1,0} convolution(bf16[8,7,7,512]{3,2,1,0} %a, bf16[3,3,512,512]{3,2,1,0} %b), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f",
     ("convolution.5", "conv")),
    ("%all-reduce.7 = f32[64]{0} all-reduce(f32[64]{0} %g), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%add",
     ("all-reduce.7", "collective")),
    ("%all-reduce-start.2 = f32[1000]{0} all-reduce-start(f32[1000]{0} %g), channel_id=9",
     ("all-reduce-start.2", "collective")),
    ("%select_and_scatter.9 = bf16[256,112,112,64]{0,3,2,1} select-and-scatter(bf16[256,112,112,64]{0,3,2,1} %f), window={size=1x3x3x1}",
     ("select_and_scatter.9", "other")),
    ("%copy = bf16[4096,224,224,3]{2,1,3,0} copy(bf16[4096,224,224,3]{0,2,3,1} %args_0_.1)",
     ("copy", "other")),
    ("conv_general_dilated.33", ("conv_general_dilated.33", "conv")),   # CPU thunk
    ("all-reduce.285", ("all-reduce.285", "collective")),               # CPU thunk
    ("convert_reverse_fusion.1", ("convert_reverse_fusion.1", "other")),
])
def test_parse_op_reads_name_and_category_from_the_hlo_text(text, want):
    assert tr.parse_op(text) == want


def _trace():
    ops = {0: [tr.Op("fusion.1", "conv", 0, 40), tr.Op("fusion.2", "other", 40, 60),
               tr.Op("all-reduce.1", "collective", 55, 70),
               tr.Op("fusion.1", "conv", 100, 140), tr.Op("fusion.2", "other", 140, 160)]}
    modules = {0: [("jit_step(1)", 0, 70), ("jit_step(1)", 100, 160),
                   ("jit_other(2)", 80, 90)]}
    host = {"zoo.readback": [(60, 110)], "zoo.dispatch": [(0, 5)]}
    async_ops = {0: [tr.Op("all-reduce-start.2", "collective", 130, 150)]}
    return tr.Trace(ops=ops, async_ops=async_ops, modules=modules, host=host)


def test_trace_summaries_on_a_hand_made_trace():
    t = _trace()
    assert t.window == (0, 160)
    assert tr.total(t.busy(0)) == 70 + 60
    assert t.busy_per_run(0, r"^jit_step\b") == [70, 60]
    s = tr.device_summary(t)
    assert s["busy_s"] == pytest.approx(130e-9) and s["window_s"] == pytest.approx(160e-9)
    assert s["idle_pct_mean"] == pytest.approx(100 * 30 / 160)
    b = tr.breakdown(t)
    assert b["device_ops"][0] == ["jit_step/fusion.1 [conv]", pytest.approx(80e-9)]
    # collective time: the synchronous op, and the async pair start to done
    assert t.collectives(0) == [(55, 70), (130, 150)]
    assert tr.exposed(t.collectives(0), t.compute(0)) == 10 + 0
    assert b["idle_gaps"] == [["zoo.readback", pytest.approx(30e-9)]]


def test_an_empty_trace_reduces_to_nothing():
    t = tr.Trace(ops={}, async_ops={}, modules={}, host={})
    assert t.window is None and tr.device_summary(t) is None and tr.breakdown(t) is None


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rb") as f:
        return tr.read_xplane(f.read())


def test_recorded_tpu_trace_has_one_device_with_ops_and_step_runs(recorded):
    assert list(recorded.ops) == [0]
    assert len(recorded.ops[0]) > 100
    runs = recorded.runs(0, r"^jit_step\b")
    assert len(runs) >= 1
    busy = recorded.busy_per_run(0, r"^jit_step\b")
    assert all(0 < b <= (e - s) for b, (s, e) in zip(busy, runs))


def test_recorded_tpu_trace_reduces_to_the_numbers_read_by_hand(recorded):
    """ResNet-18, batch 256, one v5e chip (my chip run, PR 22): the step's
    program kept the device busy for 31.7 ms, and the batch gather before
    it re-laid-out the whole 1.2 GB data set in 4.2 ms."""
    (busy,) = recorded.busy_per_run(0, r"^jit_step\b")
    assert busy / 1e6 == pytest.approx(31.7, rel=0.02)
    top = dict(map(tuple, tr.breakdown(recorded)["device_ops"]))
    assert top["jit_gather/copy"] * 1e3 == pytest.approx(4.2, rel=0.05)
    assert tr.device_summary(recorded)["idle_pct_mean"] < 1.0
    assert tr.total(recorded.collectives(0)) == 0


def test_recorded_tpu_trace_splits_convs_from_the_rest(recorded):
    cats = {o.category for o in recorded.ops[0]}
    assert {"conv", "other"} <= cats and "collective" not in cats
    conv = tr.total(tr.union(recorded.by_category(0, "conv")))
    busy = tr.total(recorded.busy(0))
    assert 0.1 < conv / busy < 0.9


def test_recorded_tpu_trace_carries_the_program_host_spans(recorded):
    assert "zoo.dispatch" in recorded.host
    s = tr.device_summary(recorded)
    assert 0 < s["busy_s"] <= s["window_s"]
    b = tr.breakdown(recorded)
    assert 1 <= len(b["device_ops"]) <= 10 and all(v > 0 for _, v in b["device_ops"])
