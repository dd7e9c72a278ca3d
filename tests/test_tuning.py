"""The unified self-tuning layer (``repro.tuning``): the plan
controller's observe/decide rules against a pure-python oracle, the
prior/measured scale separation, the roofline cost model, the
``merge_plan="auto"`` spelling end to end on three workloads, and the
offline replayability of recorded decision traces."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import datasets, make_cpu_grid
from repro.core.mlalgos import (make_linreg_step, train_linreg,
                                train_multinomial, train_svm)
from repro.distributed import merge_plan as mp
from repro.roofline import hw
from repro.distributed.compression import CompressionConfig
from repro.tuning import (AutoTune, CostModel, Measurement, PlanChoice,
                          PlanController, auto_plan, cadence_ladder,
                          candidate_choices, choice_tag,
                          compression_tag)

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# the cadence rule: PlanController.observe vs a pure-python oracle
# ---------------------------------------------------------------------------

def _oracle_cadence_trace(norms, *, k0, k_max, growth=2,
                          stable_ratio=0.5, patience=2, shrink=False,
                          spike_ratio=4.0, k_min=1):
    """Independent re-implementation of the cadence rule (the legacy
    ``_CadenceController`` grow semantics plus the optional shrink
    branch) — plain floats, no repro imports."""
    k, prev, stable = max(1, k0), None, 0
    trace = [k]
    for d in norms:
        if shrink and prev is not None and \
                d > spike_ratio * max(prev, 1e-12):
            k = max(k_min, k // 2)
            stable, prev = 0, None
            trace.append(k)
            continue
        if prev is not None:
            rel = abs(d - prev) / max(prev, 1e-12)
            stable = stable + 1 if rel <= stable_ratio else 0
        prev = d
        if stable >= patience and k < k_max:
            k = min(k * growth, k_max)
            stable, prev = 0, None
        trace.append(k)
    return trace


class TestCadenceOracle:
    def test_grow_matches_oracle(self):
        """shrink=False is the legacy grow-only rule, bit for bit."""
        norms = [1.0, 0.9, 0.85, 0.8, 2.0, 1.9, 1.85, 1.8, 1.75, 1.7]
        ctl = PlanController(k0=1, k_max=16, shrink=False)
        for d in norms:
            ctl.observe(d)
        assert ctl.cadence_trace == _oracle_cadence_trace(
            norms, k0=1, k_max=16)

    def test_spike_halves_toward_k_min(self):
        """A delta-norm spike past spike_ratio x previous halves k
        toward k_min and re-bases (no growth bookkeeping runs)."""
        ctl = PlanController(k0=8, k_max=32, shrink=True,
                             spike_ratio=4.0, k_min=2)
        ctl.observe(1.0)
        assert ctl.k == 8
        assert ctl.observe(10.0) == 4          # 10 > 4 * 1.0 -> halve
        assert ctl.observe(10.0) == 4          # prev re-based: no spike
        assert ctl.observe(100.0) == 2         # next spike halves again
        assert ctl.observe(1e4) == 2           # re-based -> not a spike
        ctl2 = PlanController(k0=2, k_max=32, shrink=True, k_min=2)
        ctl2.observe(1.0)
        assert ctl2.observe(10.0) == 2         # already at the floor

    def test_spike_resets_stability_counter(self):
        """One stable observation, then a spike: the stability streak
        must restart, so growth needs `patience` fresh observations."""
        ctl = PlanController(k0=4, k_max=32, shrink=True, patience=2)
        ctl.observe(1.0)
        ctl.observe(1.0)                       # stable = 1
        ctl.observe(10.0)                      # spike -> k=2, streak dead
        assert ctl.k == 2
        ctl.observe(10.0)                      # re-base
        ctl.observe(10.0)                      # stable = 1 -> no growth
        assert ctl.k == 2
        ctl.observe(10.0)                      # stable = 2 -> grow
        assert ctl.k == 4

    def test_shrink_disabled_ignores_spike(self):
        norms = [1.0, 50.0, 1.0, 50.0]
        ctl = PlanController(k0=4, k_max=32, shrink=False)
        for d in norms:
            ctl.observe(d)
        assert ctl.k == 4                      # spikes just reset streaks
        assert ctl.cadence_trace == _oracle_cadence_trace(
            norms, k0=4, k_max=32)

    def test_mixed_sequence_matches_oracle(self):
        """A long pseudo-random norm sequence through both
        implementations — grow, spike, re-base, grow again."""
        rng = np.random.default_rng(7)
        norms = []
        level = 1.0
        for i in range(60):
            if i % 17 == 13:
                level *= 9.0                   # occasional spike
            else:
                level *= float(rng.uniform(0.8, 1.2))
            norms.append(level)
        kwargs = dict(k0=1, k_max=16, growth=2, stable_ratio=0.5,
                      patience=2, shrink=True, spike_ratio=4.0, k_min=1)
        ctl = PlanController(**kwargs)
        for d in norms:
            ctl.observe(d)
        assert ctl.cadence_trace == _oracle_cadence_trace(
            norms, **kwargs)


# ---------------------------------------------------------------------------
# decide(): exploration queue, measured argmin, prior argmin — one scale
# at a time
# ---------------------------------------------------------------------------

_INT8 = CompressionConfig(bits=8)
_TOPK = CompressionConfig(bits=8, top_k_frac=0.25)


def _m(tag_cfg, us, *, warmup=False, delta=None):
    return Measurement(key=("plan", 1, choice_tag(tag_cfg), False),
                       seconds=us * 1e-6, steps=1, warmup=warmup,
                       delta_norm=delta)


class TestDecidePolicy:
    CHOICES = (None, _INT8, _TOPK)

    def test_prior_argmin_without_exploration(self):
        prior = {"exact": 30.0, "int8": 10.0, "top0.25@int8": 20.0}
        ctl = PlanController(k0=1, k_max=8, choices=self.CHOICES,
                             prior=prior, explore_rounds=0)
        _, choice = ctl.decide()
        assert choice_tag(choice) == "int8"
        assert not ctl._explored

    def test_exploration_probes_in_cost_order_then_exploits(self):
        prior = {"exact": 30.0, "int8": 10.0, "top0.25@int8": 20.0}
        ctl = PlanController(k0=1, k_max=8, choices=self.CHOICES,
                             prior=prior, explore_rounds=1)
        probed = []
        # each probe: decide -> head of the queue; feed one warmup (the
        # compile) then one scored round to retire it
        for _ in range(len(self.CHOICES)):
            _, choice = ctl.decide()
            probed.append(choice_tag(choice))
            ctl.observe_round(_m(choice, 100.0, warmup=True), choice)
            # measured ordering disagrees with the prior: exact is the
            # actual winner on this host
            us = {"exact": 5.0, "int8": 50.0, "top0.25@int8": 40.0}
            ctl.observe_round(_m(choice, us[choice_tag(choice)]),
                              choice)
        assert probed == ["int8", "top0.25@int8", "exact"]  # prior order
        _, choice = ctl.decide()
        assert choice_tag(choice) == "exact"           # measured won
        assert ctl.settled() is False                       # k can grow

    def test_warmup_rounds_do_not_score_or_retire_probes(self):
        ctl = PlanController(k0=1, k_max=8, choices=self.CHOICES,
                             prior={}, explore_rounds=1)
        _, choice = ctl.decide()
        ctl.observe_round(_m(choice, 999.0, warmup=True), choice)
        assert ctl.measured == {}
        _, again = ctl.decide()
        assert choice_tag(again) == choice_tag(choice)

    def test_decide_never_ranks_across_scales(self):
        """After exploration, only the measured table is consulted —
        a cheap modeled prior for an unmeasured choice must not beat a
        measured one (prior us are modeled-hardware, measured us are
        this host's wall clock: different scales)."""
        prior = {"exact": 1.0, "int8": 1000.0}   # prior loves exact
        ctl = PlanController(k0=1, k_max=8, choices=(None, _INT8),
                             prior=prior, explore_rounds=1)
        # probe only int8 by hand, then force the queue empty
        ctl.observe_round(_m(_INT8, 7.0), _INT8)
        ctl._pending = []
        _, choice = ctl.decide()
        assert choice_tag(choice) == "int8"

    def test_single_choice_short_circuits(self):
        ctl = PlanController(k0=1, k_max=8, choices=(_INT8,))
        k, choice = ctl.decide()
        assert (k, choice_tag(choice)) == (1, "int8")
        assert ctl._pending == []              # nothing to explore

    def test_best_measured_time_is_kept(self):
        ctl = PlanController(k0=1, k_max=8, choices=(None, _INT8),
                             explore_rounds=0)
        ctl.observe_round(_m(None, 20.0), None)
        ctl.observe_round(_m(None, 12.0), None)
        ctl.observe_round(_m(None, 30.0), None)
        assert ctl.measured["exact"] == pytest.approx(12.0)

    def test_prior_margin_keeps_exact_on_near_tie(self):
        """A modeled win below the margin must not move the controller
        off the exact wire: on a small wire every format's predicted
        link time ties within noise, and switching would pay real
        encode compute for a saving the model can't resolve."""
        prior = {"exact": 100.0, "int8": 99.9, "top0.25@int8": 99.8}
        ctl = PlanController(k0=1, k_max=8, choices=self.CHOICES,
                             prior=prior, explore_rounds=0,
                             prior_margin=0.05)
        _, choice = ctl.decide()
        assert choice_tag(choice) == "exact"

    def test_prior_margin_switches_on_decisive_win(self):
        prior = {"exact": 100.0, "int8": 60.0, "top0.25@int8": 90.0}
        ctl = PlanController(k0=1, k_max=8, choices=self.CHOICES,
                             prior=prior, explore_rounds=0,
                             prior_margin=0.05)
        _, choice = ctl.decide()
        assert choice_tag(choice) == "int8"

    def test_prior_margin_never_applies_to_measured(self):
        """The margin guards the modeled prior only — once real round
        times exist, the measured argmin wins however small the gap."""
        ctl = PlanController(k0=1, k_max=8, choices=(None, _INT8),
                             prior={"exact": 10.0, "int8": 11.0},
                             explore_rounds=1, prior_margin=0.5)
        ctl.observe_round(_m(None, 100.0), None)
        ctl.observe_round(_m(_INT8, 99.9), _INT8)
        ctl._pending = []
        _, choice = ctl.decide()
        assert choice_tag(choice) == "int8"

    def test_prior_margin_zero_recovers_bare_argmin(self):
        prior = {"exact": 100.0, "int8": 99.9, "top0.25@int8": 99.95}
        ctl = PlanController(k0=1, k_max=8, choices=self.CHOICES,
                             prior=prior, explore_rounds=0,
                             prior_margin=0.0)
        _, choice = ctl.decide()
        assert choice_tag(choice) == "int8"


class TestOverlapAxis:
    """The overlap candidate axis: every wire format is offered with
    and without the deferred-commit pipeline, overlap variants are
    probed like any other candidate, and only measured evidence (never
    the single-chip prior, which models no win) can promote one."""

    OV = (PlanChoice(None), PlanChoice(None, True),
          PlanChoice(_INT8), PlanChoice(_INT8, True))

    def test_choice_tags(self):
        assert choice_tag(PlanChoice(None)) == "exact"
        assert choice_tag(PlanChoice(None, True)) == "exact+ov"
        assert choice_tag(PlanChoice(_INT8, True)) == "int8+ov"
        assert choice_tag(PlanChoice(_TOPK)) == "top0.25@int8"
        # legacy bare configs normalize to overlap-off
        assert choice_tag(None) == "exact"
        assert choice_tag(_INT8) == "int8"

    def test_overlap_variants_probe_separately(self):
        """Each overlap variant is its own exploration probe with its
        own measured slot — never folded into its non-overlap twin."""
        prior = {"exact": 10.0, "exact+ov": 20.0,
                 "int8": 30.0, "int8+ov": 40.0}
        ctl = PlanController(k0=1, k_max=8, choices=self.OV,
                             prior=prior, explore_rounds=1)
        probed = []
        for _ in range(len(self.OV)):
            _, choice = ctl.decide()
            probed.append(choice_tag(choice))
            ctl.observe_round(_m(choice, 100.0, warmup=True), choice)
            us = {"exact": 50.0, "exact+ov": 5.0,
                  "int8": 60.0, "int8+ov": 70.0}
            ctl.observe_round(_m(choice, us[choice_tag(choice)]),
                              choice)
        assert probed == ["exact", "exact+ov", "int8", "int8+ov"]
        assert set(ctl.measured) == set(us)

    def test_measured_evidence_promotes_overlap(self):
        """After exploration the measured argmin may be an overlap
        variant — wall-clock evidence wins."""
        ctl = PlanController(k0=1, k_max=8, choices=self.OV,
                             explore_rounds=1)
        ctl.observe_round(_m(PlanChoice(None), 50.0), PlanChoice(None))
        ctl.observe_round(_m(PlanChoice(None, True), 5.0),
                          PlanChoice(None, True))
        ctl._pending = []
        _, choice = ctl.decide()
        assert choice == PlanChoice(None, True)
        assert choice.overlap is True

    def test_prior_tie_never_proposes_overlap(self):
        """On a single-chip grid the prior ties overlap with its twin
        (CostModel models no win there): an unexplored fit must stay on
        the plain exact wire, not drift onto the pipeline on a tie."""
        prior = {"exact": 100.0, "exact+ov": 100.0,
                 "int8": 99.9, "int8+ov": 99.9}
        ctl = PlanController(k0=1, k_max=8, choices=self.OV,
                             prior=prior, explore_rounds=0,
                             prior_margin=0.05)
        _, choice = ctl.decide()
        assert choice == PlanChoice(None, False)

    def test_prior_decisive_overlap_win_switches(self):
        """A real modeled win past the margin (a multi-chip grid where
        overlap hides DCN time) may pick the overlap variant from the
        prior alone."""
        prior = {"exact": 100.0, "exact+ov": 60.0,
                 "int8": 95.0, "int8+ov": 90.0}
        ctl = PlanController(k0=1, k_max=8, choices=self.OV,
                             prior=prior, explore_rounds=0,
                             prior_margin=0.05)
        _, choice = ctl.decide()
        assert choice == PlanChoice(None, True)

    def test_chosen_records_overlap(self):
        ctl = PlanController(k0=1, k_max=8,
                             choices=(PlanChoice(None, True),))
        ctl.decide()
        assert ctl.chosen() == {"cadence": 1, "compression": "exact+ov",
                                "overlap": True}


class TestLaddersAndChoices:
    def test_cadence_ladder(self):
        assert cadence_ladder(1, 32, 2) == [1, 2, 4, 8, 16, 32]
        assert cadence_ladder(3, 8, 2) == [3, 6, 8]
        assert cadence_ladder(8, 8, 2) == [8]

    def test_candidate_choices_auto_unpinned(self):
        choices = candidate_choices(AutoTune(), None)
        tags = [choice_tag(c) for c in choices]
        assert tags == ["exact", "exact+ov", "int8", "int8+ov",
                        "top0.25@int8", "top0.25@int8+ov",
                        "top0.125@int8", "top0.125@int8+ov"]

    def test_candidate_choices_pinned_compression(self):
        """Pinning the wire collapses the whole grid — including the
        overlap axis — to one overlap-off choice."""
        choices = candidate_choices(AutoTune(), _INT8)
        assert choices == [PlanChoice(_INT8)]

    def test_candidate_choices_non_auto_preset(self):
        choices = candidate_choices(mp.AdaptiveCadence(), None)
        assert choices == [PlanChoice(None)]

    def test_autotune_preset_validation(self):
        with pytest.raises(ValueError):
            AutoTune(k_min=9, k_max=8)
        with pytest.raises(ValueError):
            AutoTune(spike_ratio=1.0)
        with pytest.raises(ValueError):
            AutoTune(growth=1)
        with pytest.raises(ValueError):
            AutoTune(prior_margin=1.0)
        with pytest.raises(ValueError):
            AutoTune(prior_margin=-0.1)


class TestResolveSpelling:
    def test_auto_string_resolves_to_preset(self):
        plan = mp.MergePlan.resolve("auto")
        assert plan.auto and isinstance(plan.outer, AutoTune)

    def test_unknown_string_rejected(self):
        with pytest.raises(ValueError, match="unknown merge_plan"):
            mp.MergePlan.resolve("aggressive")

    def test_auto_string_rejects_legacy_kwargs(self):
        with pytest.raises(ValueError, match="not both"):
            mp.MergePlan.resolve("auto", merge_every=4)

    def test_auto_plan_helper_forwards_kwargs(self):
        plan = auto_plan(k_max=4, shrink=False)
        assert plan.outer.k_max == 4 and plan.outer.shrink is False


# ---------------------------------------------------------------------------
# the roofline cost model
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def linreg_setup():
    X, y, _ = datasets.regression(KEY, 256, 8)
    grid = make_cpu_grid(4)
    data, n, lf, uf, w0 = make_linreg_step(grid, X, y, lr=0.05)
    return grid, data, lf, uf, w0


class TestCostModel:
    def test_wire_bytes_ordering(self, linreg_setup):
        grid, data, lf, uf, w0 = linreg_setup
        model = CostModel.for_fit(grid, lf, uf, w0, data)
        exact = model.wire_bytes(None)
        int8 = model.wire_bytes(_INT8)
        topk = model.wire_bytes(_TOPK)
        assert exact > int8 > 0
        assert topk < exact

    def test_predicted_us_per_step_falls_with_cadence(self, linreg_setup):
        """u(k) = t_local + t_merge / k — monotone non-increasing in k,
        the shape the bench_scaling fitted model shares."""
        grid, data, lf, uf, w0 = linreg_setup
        model = CostModel.for_fit(grid, lf, uf, w0, data)
        us = [model.predict(cadence=k)["us_per_step"]
              for k in (1, 2, 4, 8)]
        assert all(a >= b for a, b in zip(us, us[1:]))
        assert us[-1] > 0

    def test_prediction_is_a_prior_measurement(self, linreg_setup):
        grid, data, lf, uf, w0 = linreg_setup
        model = CostModel.for_fit(grid, lf, uf, w0, data)
        m = model.prediction(cadence=4, compression=_INT8)
        assert m.source == "prior" and m.steps == 4
        assert m.key == ("plan", 4, "int8", False)
        assert m.us_per_step() == pytest.approx(
            model.predict(cadence=4, compression=_INT8)["us_per_step"])

    def test_table_sorted_best_first(self, linreg_setup):
        grid, data, lf, uf, w0 = linreg_setup
        model = CostModel.for_fit(grid, lf, uf, w0, data)
        rows = model.table(cadences=(1, 4), compressions=(None, _INT8))
        assert len(rows) == 4
        us = [r["us_per_step"] for r in rows]
        assert us == sorted(us)
        assert {(r["cadence"], r["compression"]) for r in rows} == \
            {(1, "exact"), (1, "int8"), (4, "exact"), (4, "int8")}

    def test_single_chip_prior_models_no_overlap_win(self, linreg_setup):
        """On the emulated (single-chip) grid there is no second
        execution stream: overlap=True must predict exactly the
        non-overlap time (only a measured probe can promote it), while
        still being tagged as the overlap variant."""
        grid, data, lf, uf, w0 = linreg_setup
        model = CostModel.for_fit(grid, lf, uf, w0, data)
        plain = model.predict(cadence=2)
        ov = model.predict(cadence=2, overlap=True)
        assert ov["overlap"] is True and plain["overlap"] is False
        assert ov["us_per_step"] == pytest.approx(plain["us_per_step"])
        rows = model.table(cadences=(1, 2), compressions=(None,),
                          overlaps=(False, True))
        assert len(rows) == 4
        assert {r["overlap"] for r in rows} == {False, True}

    def test_model_cached_on_grid(self, linreg_setup):
        grid, data, lf, uf, w0 = linreg_setup
        m1 = CostModel.for_fit(grid, lf, uf, w0, data)
        m2 = CostModel.for_fit(grid, lf, uf, w0, data)
        assert m1 is m2

    def test_compression_tags(self):
        assert compression_tag(None) == "exact"
        assert compression_tag(_INT8) == "int8"
        assert compression_tag(_TOPK) == "top0.25@int8"
        assert compression_tag(
            CompressionConfig(bits=None, top_k_frac=0.5)) == "top0.5@raw"


# ---------------------------------------------------------------------------
# fit(merge_plan="auto") end to end
# ---------------------------------------------------------------------------

# small preset so tests explore + settle inside a few dozen steps
_FAST = dict(k_max=4, min_steps_to_explore=8, hold_rounds=2,
             top_k_rungs=1)

_TRACE_KEYS = {"choices", "prior_margin", "prior_us_per_step",
               "measured_us_per_step", "cost_table", "decisions",
               "chosen", "cadence_trace"}


class TestChipPeaks:
    def test_cpu_models_the_v5e_target(self):
        assert hw.peaks_for(jax.devices("cpu")[0]) is hw.TPU_V5E

    def test_v5e_by_device_kind(self):
        dev = types.SimpleNamespace(platform="tpu",
                                    device_kind="TPU v5 lite")
        peaks = hw.peaks_for(dev)
        assert (peaks.flops_bf16, peaks.ops_int8, peaks.hbm_bw) == (
            197e12, 393e12, 819e9)

    def test_unknown_tpu_is_an_error(self):
        dev = types.SimpleNamespace(platform="tpu",
                                    device_kind="TPU v99")
        with pytest.raises(ValueError, match="TPU v99"):
            hw.peaks_for(dev)


class TestAutoFit:
    def test_linreg_auto_converges_with_trace(self):
        X, y, _ = datasets.regression(KEY, 256, 6)
        grid = make_cpu_grid(4)
        ms = {}
        res = train_linreg(grid, X, y, lr=0.05, steps=40,
                           merge_plan=auto_plan(**_FAST),
                           merge_state=ms)
        assert len(res.history) == 40
        assert float(np.mean(np.asarray(res.history[-1]["loss"]))) < \
            float(np.mean(np.asarray(res.history[0]["loss"])))
        trace = ms["tuning_trace"]
        assert set(trace) == _TRACE_KEYS
        assert trace["choices"] == ["exact", "exact+ov",
                                    "int8", "int8+ov",
                                    "top0.25@int8", "top0.25@int8+ov"]
        assert trace["chosen"]["compression"] in trace["choices"]
        assert 1 <= trace["chosen"]["cadence"] <= 4
        # every decision row is replayable: full bookkeeping present
        for row in trace["decisions"]:
            assert {"round", "steps_done", "cadence", "compression",
                    "overlap", "warmup", "us_per_step", "delta_norm",
                    "rounds_in_dispatch",
                    "predicted_us_per_step"} <= set(row)
        assert trace["decisions"][-1]["steps_done"] == 40
        # exploration visited the overlap variants (the probe rounds
        # drive the deferred-commit dispatch path end to end)
        assert any(d["overlap"] for d in trace["decisions"])
        # the cost table ranks wires x overlap x the cadence ladder
        assert len(trace["cost_table"]) == 3 * 2 * len(
            cadence_ladder(1, 4, 2))

    def test_auto_string_spelling_via_train(self):
        X, y, _ = datasets.regression(KEY, 128, 4)
        grid = make_cpu_grid(4)
        ms = {}
        res = train_linreg(grid, X, y, lr=0.05, steps=12,
                           merge_plan="auto", merge_state=ms)
        # short fit: no exploration (min_steps_to_explore=96), the
        # prior picks the wire — but the trace still lands
        assert len(res.history) == 12
        assert ms["tuning_trace"]["chosen"]["compression"] in \
            ms["tuning_trace"]["choices"]
        assert ms["tuning_trace"]["decisions"][0]["warmup"] is True

    def test_short_fit_prior_stays_on_exact_wire(self):
        """An unexplored fit must not leave the exact wire: on this
        grid the wire is a few hundred bytes, so the modeled link times
        of exact/int8/top-k tie within nanoseconds and only the
        prior_margin rule stands between the controller and paying
        real quantize/sparsify compute for a fictional saving.  This is
        the bench_scaling timed-cell configuration — pinned here so the
        artifact's auto cells track the exact-wire adaptive cells."""
        X, y, _ = datasets.regression(KEY, 256, 6)
        grid = make_cpu_grid(4)
        ms = {}
        train_linreg(grid, X, y, lr=0.05, steps=24,
                     merge_plan="auto", merge_state=ms)
        trace = ms["tuning_trace"]
        assert trace["prior_margin"] == pytest.approx(0.05)
        assert trace["chosen"]["compression"] == "exact"
        assert all(d["compression"] == "exact"
                   for d in trace["decisions"])
        assert trace["measured_us_per_step"].keys() <= {"exact"}

    def test_svm_auto(self):
        X, y, _ = datasets.binary_classification(KEY, 256, 6)
        grid = make_cpu_grid(4)
        ms = {}
        res = train_svm(grid, X, y, lr=0.3, steps=32,
                        merge_plan=auto_plan(**_FAST), merge_state=ms)
        assert len(res.history) == 32
        from repro.core.mlalgos.svm import svm_accuracy
        assert svm_accuracy(res.w, X, y) > 0.7
        assert ms["tuning_trace"]["decisions"]

    def test_multinomial_auto(self):
        X, y = datasets.mixture_classification(KEY, 300, 6, 3)
        grid = make_cpu_grid(4)
        ms = {}
        res = train_multinomial(grid, X, y, n_classes=3, lr=0.5,
                                steps=32, merge_plan=auto_plan(**_FAST),
                                merge_state=ms)
        assert len(res.history) == 32
        from repro.core.mlalgos.multinomial import multinomial_accuracy
        assert multinomial_accuracy(res.W, X, y) > 0.5
        assert ms["tuning_trace"]["chosen"]["cadence"] >= 1

    def test_pinned_compression_leaves_only_cadence(self):
        X, y, _ = datasets.regression(KEY, 128, 4)
        grid = make_cpu_grid(4)
        ms = {}
        plan = mp.MergePlan(compression=_INT8, outer=AutoTune(**_FAST))
        train_linreg(grid, X, y, lr=0.05, steps=16, merge_plan=plan,
                     merge_state=ms)
        trace = ms["tuning_trace"]
        assert trace["choices"] == ["int8"]
        assert all(d["compression"] == "int8"
                   for d in trace["decisions"])

    def test_trace_replays_offline(self):
        """The recorded decisions carry enough to replay the cadence
        sequence through a fresh controller — the reproducibility
        contract of merge_state['tuning_trace']."""
        X, y, _ = datasets.regression(KEY, 256, 6)
        grid = make_cpu_grid(4)
        ms = {}
        preset = AutoTune(k_max=8, min_steps_to_explore=10 ** 9,
                          hold_rounds=1)
        train_linreg(grid, X, y, lr=0.05, steps=48,
                     merge_plan=mp.MergePlan(outer=preset),
                     merge_state=ms)
        trace = ms["tuning_trace"]
        replay = PlanController(
            k0=1, k_max=preset.k_max, growth=preset.growth,
            stable_ratio=preset.stable_ratio, patience=preset.patience,
            shrink=preset.shrink, spike_ratio=preset.spike_ratio,
            k_min=preset.k_min)
        for row in trace["decisions"]:
            replay.observe(row["delta_norm"])
        assert replay.cadence_trace == trace["cadence_trace"]

    def test_adaptive_preset_rides_the_same_controller(self):
        """AdaptiveCadence is a thin preset of PlanController: the
        legacy cadence_trace slot still fills, with grow-only
        semantics (no shrink, single exact choice)."""
        X, y, _ = datasets.regression(KEY, 256, 6)
        grid = make_cpu_grid(4)
        ms = {}
        res = train_linreg(
            grid, X, y, lr=0.05, steps=48,
            merge_plan=mp.MergePlan(outer=mp.AdaptiveCadence(k_max=8)),
            merge_state=ms)
        assert len(res.history) == 48
        trace = ms["cadence_trace"]
        assert trace[0] == 1
        assert all(b >= a for a, b in zip(trace, trace[1:]))
        assert ms["tuning_trace"]["choices"] == ["exact"]
        # replay against the oracle from the recorded delta norms
        norms = [d["delta_norm"] for d in ms["tuning_trace"]["decisions"]]
        assert trace == _oracle_cadence_trace(norms, k0=1, k_max=8)

    def test_auto_runner_cache_shared_across_fits(self):
        """A second auto fit on the same grid/functions reuses the
        compiled controller runners — the cache must not grow."""
        X, y, _ = datasets.regression(KEY, 128, 4)
        grid = make_cpu_grid(4)
        train_linreg(grid, X, y, lr=0.05, steps=12,
                     merge_plan=auto_plan(**_FAST), merge_state={})
        size = len(grid._fit_cache)
        train_linreg(grid, X, y, lr=0.05, steps=12,
                     merge_plan=auto_plan(**_FAST), merge_state={})
        assert len(grid._fit_cache) == size

    def test_default_plan_untouched_by_tuning_import(self):
        """merge_plan=None keeps the bit-exact engine: same bits as
        the python-loop oracle, no tuning_trace written."""
        X, y, _ = datasets.regression(KEY, 128, 4)
        grid = make_cpu_grid(4)
        ms = {}
        r_def = train_linreg(grid, X, y, lr=0.05, steps=10,
                             merge_state=ms)
        r_py = train_linreg(grid, X, y, lr=0.05, steps=10,
                            engine="python")
        np.testing.assert_array_equal(np.asarray(r_def.w),
                                      np.asarray(r_py.w))
        assert "tuning_trace" not in ms
