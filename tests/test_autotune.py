"""Cost-model autotuner + predictive capacity planner (ISSUE 17).

Three layers under one marker:

- the search (analysis/autotune.py): legality/canonicalization of the
  plan space, the admissible prune (brute-force equality), the hard HBM
  budget, deterministic ranking;
- the artifacts: cost_report.json autotune section round-trip, the
  schema-version ratchet (stale artifacts fail loudly), AutotuneConfig
  env layering, the hardware-profile registry;
- the serve side (serve/capacity.py + the autoscaler's feed-forward
  branch): hand-computed replicas-needed, cold starts, and the
  predictive scale-up landing with NO hysteresis while the reactive
  classifier is silent.

Everything here is CPU-pure — no jax tracing, no sockets; a measured
ranking to set beside the model's does not exist yet (ROADMAP Design 9).
"""

import json

import pytest

from parallel_cnn_tpu.analysis import autotune, cost_model, hw_profiles
from parallel_cnn_tpu.config import (
    AutotuneConfig,
    CommConfig,
    FusedStepConfig,
    PipelineConfig,
)
from parallel_cnn_tpu.serve.admission import AdmissionController
from parallel_cnn_tpu.serve.autoscaler import AutoScaler
from parallel_cnn_tpu.serve.capacity import CapacityModel

pytestmark = pytest.mark.autotune

_MIB = 1024 * 1024

# A synthetic profile shaped like the small CNNs the repo trains: enough
# flops that overlap matters, enough params that HBM budgets can bite.
MP = autotune.ModelProfile(
    name="toy",
    param_elems=1_048_576,
    param_bytes=4 * 1_048_576,
    mstate_bytes=8_192,
    flops_per_image=3_000_000_000,
    act_bytes_per_image=2_000_000,
    wire_numel=4_096,
    layer_fwd_flops=(500_000_000, 500_000_000),
)
HW = hw_profiles.get_profile("v5e-8")


def _search(**kw):
    kw.setdefault("global_batch", 128)
    kw.setdefault("n_dev", 8)
    return autotune.search(MP, hw=HW, **kw)


# ---------------------------------------------------------------------------
# the search


class TestSearch:
    def test_pruned_topk_equals_brute_force(self):
        """The compute-only lower bound is admissible, so pruning must
        not change the top-k by even a tie-break."""
        pruned = _search(prune=True, top_k=8)
        brute = _search(prune=False, top_k=8)
        assert [s.plan for s in pruned.ranked] == \
            [s.plan for s in brute.ranked]
        assert [s.img_s for s in pruned.ranked] == \
            [s.img_s for s in brute.ranked]

    def test_deterministic_ranking(self):
        a, b = _search(top_k=8), _search(top_k=8)
        assert [s.plan for s in a.ranked] == [s.plan for s in b.ranked]

    def test_hbm_budget_excludes_but_keeps_feasible(self):
        full = _search(prune=False, top_k=10_000)
        peaks = sorted(s.peak_hbm for s in full.ranked)
        budget = peaks[len(peaks) // 2]  # median: some in, some out
        tight = _search(hbm_budget=budget, top_k=10_000)
        assert len(tight.excluded_hbm) > 0
        assert all(s.peak_hbm <= budget for s in tight.ranked)
        assert all(peak > budget for _, peak in tight.excluded_hbm)
        assert tight.n_feasible == tight.n_enumerated - \
            len(tight.excluded_hbm)

    def test_impossible_budget_raises_no_feasible_plan(self):
        with pytest.raises(autotune.NoFeasiblePlan):
            _search(hbm_budget=1)

    def test_assert_within_budget_both_ways(self):
        plan = _search().chosen.plan
        peak = autotune.assert_within_budget(
            plan, MP, global_batch=128, n_dev=8, hw=HW
        )
        assert peak > 0
        with pytest.raises(autotune.BudgetExceeded):
            autotune.assert_within_budget(
                plan, MP, global_batch=128, n_dev=8, hbm_budget=1024
            )

    def test_bubble_makes_pipeline_compute_slower(self):
        """(M+S-1)/M: compute time strictly grows with stages at fixed
        accum, and shrinks as accum amortizes the bubble."""
        t = {
            s: autotune._compute_time(
                autotune.Plan(stages=s, accum=4), MP, HW,
                global_batch=128, n_dev=8, n_host=1,
            )
            for s in (1, 2, 4)
        }
        assert t[1] < t[2] < t[4]
        t_k8 = autotune._compute_time(
            autotune.Plan(stages=4, accum=8), MP, HW,
            global_batch=128, n_dev=8, n_host=1,
        )
        assert t_k8 < t[4]

    def test_overlap_wins_when_compute_bound(self):
        """For a compute-bound profile the overlapped ring hides its
        (K+1)-pass comm entirely: max() beats sum()."""
        kw = dict(global_batch=128, n_dev=8)
        ovl = autotune.score_plan(
            autotune.Plan(comm_impl="ring", overlap=True, accum=2),
            MP, HW, **kw)
        post = autotune.score_plan(
            autotune.Plan(comm_impl="ring", overlap=False, accum=2),
            MP, HW, **kw)
        assert ovl.t_compute_s >= ovl.t_comm_s  # compute-bound premise
        assert ovl.img_s > post.img_s

    def test_choose_for_trace_ignores_env_profile(self, monkeypatch):
        """The traced entry must be byte-stable across environments, so
        the trace chooser pins the DEFAULT profile even when
        PCNN_HW_PROFILE points elsewhere."""
        base = autotune.choose_for_trace(MP, n_dev=8, global_batch=128)
        monkeypatch.setenv("PCNN_HW_PROFILE", "cpu-emu")
        env = autotune.choose_for_trace(MP, n_dev=8, global_batch=128)
        assert env.plan == base.plan
        assert env.img_s == base.img_s
        assert env.plan.stages == 1 and env.plan.zero == 0


# ---------------------------------------------------------------------------
# artifacts: report round-trip, schema ratchet, config layering


class TestArtifacts:
    def test_section_write_load_roundtrip(self, tmp_path):
        res = _search(top_k=4)
        report = tmp_path / "cost_report.json"
        autotune.write_section(report, autotune.build_section(res))
        plan, section = autotune.load_chosen_plan(report)
        assert plan == res.chosen.plan
        assert section["n_dev"] == 8
        assert section["global_batch"] == 128
        assert len(section["ranked"]) == 4
        # the merged report keeps the schema version
        assert json.loads(report.read_text())["version"] == \
            cost_model.COST_SCHEMA_VERSION

    def test_autotune_fills_mesh_from_scored_shape(self, tmp_path):
        # The (n_dev, n_host) the tuner scored is part of the plan: a
        # flat single-stage plan activates pure DP over the scored
        # device count; an explicit mesh flag still wins.
        from parallel_cnn_tpu import cli

        report = tmp_path / "cost_report.json"
        autotune.write_section(report, autotune.build_section(_search()))
        p = cli.build_parser()
        cfg = cli.config_from_args(p.parse_args(
            ["--model", "cifar_cnn", "--autotune-report", str(report)]))
        assert cfg.mesh.data == 8 and cfg.mesh.model == 1
        assert cfg.comm is not None
        cfg2 = cli.config_from_args(p.parse_args(
            ["--model", "cifar_cnn", "--autotune-report", str(report),
             "--mesh-data", "4"]))
        assert cfg2.mesh.data == 4
        # the lenet reference path has no mesh to activate
        cfg3 = cli.config_from_args(p.parse_args(
            ["--model", "lenet_ref", "--autotune-report", str(report)]))
        assert cfg3.mesh.data is None

    def test_write_section_preserves_traced_entries(self, tmp_path):
        report = tmp_path / "cost_report.json"
        cost_model.write_cost_report(report, {"zoo.step": {"ici": 1}})
        autotune.write_section(
            report, autotune.build_section(_search(top_k=2))
        )
        data = cost_model.load_cost_report(report)
        assert data["entries"] == {"zoo.step": {"ici": 1}}
        assert "autotune" in data

    def test_missing_report_and_missing_section_fail_loudly(self, tmp_path):
        with pytest.raises(autotune.NoFeasiblePlan, match="tune"):
            autotune.load_chosen_plan(tmp_path / "nope.json")
        report = tmp_path / "cost_report.json"
        cost_model.write_cost_report(report, {})  # no autotune section
        with pytest.raises(autotune.NoFeasiblePlan, match="autotune"):
            autotune.load_chosen_plan(report)

    def test_schema_version_mismatch_rejected(self, tmp_path):
        stale = tmp_path / "stale.json"
        stale.write_text(json.dumps({"version": 0, "entries": {}}))
        with pytest.raises(cost_model.CostSchemaError):
            cost_model.load_cost_report(stale)
        with pytest.raises(cost_model.CostSchemaError):
            cost_model.load_cost_baseline(stale)
        with pytest.raises(cost_model.CostSchemaError):
            autotune.load_chosen_plan(stale)

    def test_plan_json_roundtrip(self):
        for sc in _search(top_k=8).ranked:
            assert autotune.Plan.from_json(sc.plan.to_json()) == sc.plan

    def test_plan_to_configs_mapping(self):
        comm, fused, pipe, accum = autotune.plan_to_configs(
            autotune.Plan(comm_impl="ring", bucket_bytes=_MIB,
                          wire_dtype="bfloat16", overlap=True, accum=4)
        )
        assert isinstance(comm, CommConfig)
        assert (comm.impl, comm.bucket_bytes, comm.wire_dtype,
                comm.overlap) == ("ring", _MIB, "bfloat16", True)
        assert fused is None and pipe is None and accum == 4

        comm, fused, pipe, _ = autotune.plan_to_configs(
            autotune.Plan(comm_impl="ring", zero=2, fused=True,
                          overlap=False)
        )
        assert isinstance(fused, FusedStepConfig) and fused.zero == 2
        assert comm.overlap  # ZeRO schedules are inherently overlapped

        _, _, pipe, _ = autotune.plan_to_configs(
            autotune.Plan(comm_impl="ring", overlap=False, stages=4,
                          accum=4)
        )
        assert isinstance(pipe, PipelineConfig) and pipe.stages == 4

    def test_autotune_config_env_layering(self, monkeypatch):
        for var in ("PCNN_AUTOTUNE", "PCNN_AUTOTUNE_REPORT",
                    "PCNN_AUTOTUNE_TOPK", "PCNN_AUTOTUNE_HBM_BUDGET"):
            monkeypatch.delenv(var, raising=False)
        assert AutotuneConfig.from_env() is None  # absent ≠ disabled
        monkeypatch.setenv("PCNN_AUTOTUNE", "1")
        monkeypatch.setenv("PCNN_AUTOTUNE_TOPK", "3")
        at = AutotuneConfig.from_env()
        assert at.enabled and at.top_k == 3
        # None = resolve to the shipped report (DEFAULT_COST_REPORT) at
        # use; an explicit env path survives verbatim.
        assert at.report is None
        monkeypatch.setenv("PCNN_AUTOTUNE_REPORT", "/tmp/other.json")
        assert AutotuneConfig.from_env().report == "/tmp/other.json"
        monkeypatch.delenv("PCNN_AUTOTUNE_REPORT")
        monkeypatch.setenv("PCNN_AUTOTUNE", "0")
        assert not AutotuneConfig.from_env().enabled
        with pytest.raises(ValueError):
            AutotuneConfig(top_k=0)
        with pytest.raises(ValueError):
            AutotuneConfig(hw="not-a-profile")

    def test_hw_profiles_registry(self, monkeypatch):
        monkeypatch.delenv("PCNN_HW_PROFILE", raising=False)
        default = hw_profiles.get_profile()
        assert default.name == hw_profiles.DEFAULT_PROFILE == "v5e-8"
        # the historical constants check --cost always pinned
        assert default.peak_flops == 197e12
        assert default.ici_bytes_per_s == 9.0e10
        assert default.dcn_bytes_per_s == 2.5e10
        assert hw_profiles.get_profile("v4").peak_flops == 275e12
        monkeypatch.setenv("PCNN_HW_PROFILE", "cpu-emu")
        assert hw_profiles.active_profile().name == "cpu-emu"
        with pytest.raises(ValueError, match="unknown hardware profile"):
            hw_profiles.get_profile("v999")


# ---------------------------------------------------------------------------
# serve side: capacity model + the predictive autoscaler branch


class _FakeAdmission:
    """Just enough AdmissionController surface for CapacityModel."""

    def __init__(self, rate=0.0, service_ms=None):
        self.rate = rate
        self.service_ms = service_ms or {}

    def arrival_rate(self):
        return self.rate

    def snapshot(self):
        return {"service_ewma_ms": dict(self.service_ms)}


class TestCapacityModel:
    def test_hand_computed_replicas(self):
        """λ=50 rps, best bucket 8 @ 400 ms → μ=20 rps; headroom 0.5
        → ceil(50 / 10) = 5 replicas."""
        cap = CapacityModel(
            _FakeAdmission(rate=50.0, service_ms={1: 100.0, 8: 400.0}),
            max_batch=8, headroom=0.5,
        )
        assert cap.service_rate() == pytest.approx(20.0)
        assert cap.replicas_needed() == 5

    def test_buckets_above_max_batch_do_not_count(self):
        cap = CapacityModel(
            _FakeAdmission(rate=50.0, service_ms={1: 100.0, 8: 400.0}),
            max_batch=4, headroom=1.0,
        )
        assert cap.service_rate() == pytest.approx(10.0)  # only bucket 1
        assert cap.replicas_needed() == 5

    def test_cold_estimates_return_none(self):
        assert CapacityModel(
            _FakeAdmission(), max_batch=8
        ).replicas_needed() is None
        assert CapacityModel(
            _FakeAdmission(rate=10.0), max_batch=8
        ).replicas_needed() is None  # no service estimate yet

    def test_floor_is_one_replica(self):
        cap = CapacityModel(
            _FakeAdmission(rate=0.001, service_ms={8: 1.0}),
            max_batch=8, headroom=1.0,
        )
        assert cap.replicas_needed() == 1

    def test_validation(self):
        with pytest.raises(ValueError):
            CapacityModel(_FakeAdmission(), max_batch=0)
        with pytest.raises(ValueError):
            CapacityModel(_FakeAdmission(), max_batch=8, headroom=0.0)
        with pytest.raises(ValueError):
            CapacityModel(_FakeAdmission(), max_batch=8, headroom=1.5)

    def test_arrival_rate_ewma_converges(self):
        """Steady 100 Hz offered load (admitted or not) converges the
        interarrival EWMA → arrival_rate ≈ 100 rps."""
        t = [0.0]
        ac = AdmissionController(
            slo_ms=100.0, queue_depth=16, clock=lambda: t[0]
        )
        assert ac.arrival_rate() == 0.0  # cold
        for _ in range(200):
            t[0] += 0.01
            ac.admit(priority="guaranteed", deadline=None, queue_depth=0)
        assert ac.arrival_rate() == pytest.approx(100.0, rel=0.05)
        assert ac.snapshot()["arrival_rate_rps"] == \
            pytest.approx(100.0, rel=0.05)

    def test_snapshot_shape(self):
        snap = CapacityModel(
            _FakeAdmission(rate=50.0, service_ms={8: 400.0}),
            max_batch=8, headroom=0.5,
        ).snapshot()
        assert snap["replicas_needed"] == 5
        assert snap["headroom"] == 0.5
        assert snap["max_batch"] == 8


class _ScriptedStats:
    def __init__(self):
        self.shed, self.p99, self.occ = 0.0, None, None

    def window_shed_rate(self):
        return self.shed

    def window_p99_ms(self):
        return self.p99

    def window_occupancy(self):
        return self.occ


class _FakePool:
    def __init__(self, n=1, cap=4):
        self.slots = [True] * n + [False] * (cap - n)

    @property
    def n_replicas(self):
        return len(self.slots)

    def routable(self):
        return [i for i, a in enumerate(self.slots) if a]

    def grow(self, device=None):
        i = self.slots.index(False)
        self.slots[i] = True
        return i


class _FakeBatcher:
    def __init__(self, stats):
        self.stats = stats
        self.n_runners = 99  # growth never needs new runners here

    def inflight(self, replica):
        return 0


class _FixedCapacity:
    def __init__(self, needed):
        self.needed = needed

    def replicas_needed(self):
        return self.needed


class TestPredictiveAutoscaler:
    def _scaler(self, capacity, **kw):
        t = [0.0]
        stats = _ScriptedStats()
        kw.setdefault("max_replicas", 4)
        kw.setdefault("hysteresis", 5)  # reactive path cannot fire fast
        kw.setdefault("cooldown_s", 1.0)
        sc = AutoScaler(_FakePool(n=1, cap=4), _FakeBatcher(stats),
                        capacity=capacity, clock=lambda: t[0], **kw)
        return sc, stats, t

    def test_predictive_scale_up_skips_hysteresis(self):
        """One tick, zero overload symptoms, hysteresis=5: only the
        feed-forward branch can have acted."""
        sc, stats, t = self._scaler(_FixedCapacity(3))
        t[0] = 0.1
        assert sc.tick() == "up"
        assert sc.snapshot()["predictive_ups"] == 1
        assert stats.shed == 0.0 and stats.p99 is None  # no symptom

    def test_predictive_honours_cooldown_and_max(self):
        sc, _, t = self._scaler(_FixedCapacity(10), cooldown_s=1.0)
        t[0] = 0.1
        assert sc.tick() == "up"
        t[0] = 0.5
        assert sc.tick() is None  # inside cooldown
        for step in range(2, 8):
            t[0] = float(step) * 1.1
            sc.tick()
        snap = sc.snapshot()
        assert snap["routable"] == snap["max"] == 4  # clamped
        assert snap["predictive_ups"] == 3  # 1 → 4 replicas

    def test_cold_planner_falls_back_to_reactive(self):
        """replicas_needed()=None: the loop is exactly the PR 11
        reactive scaler — acts only after the hysteresis streak, and
        counts zero predictive ups."""
        sc, stats, t = self._scaler(_FixedCapacity(None), hysteresis=2)
        stats.shed = 0.5  # reactive overload symptom
        ticks_to_act = 0
        for step in range(1, 6):
            t[0] = float(step) * 0.1
            if sc.tick() == "up":
                ticks_to_act = step
                break
        assert ticks_to_act == 2  # the hysteresis streak, not tick 1
        assert sc.snapshot()["predictive_ups"] == 0

    def test_satisfied_planner_never_acts(self):
        sc, _, t = self._scaler(_FixedCapacity(1))
        for step in range(1, 6):
            t[0] = float(step)
            assert sc.tick() is None
        assert sc.actions == []
