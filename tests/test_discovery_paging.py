"""DISCOVER (Eq. 7/8) + AI PAGING (Eq. 9) behaviour."""

import dataclasses

import pytest

from repro.core.analytics import Analytics
from repro.core.asp import MobilityClass, QualityTier, default_asp
from repro.core.catalog import default_catalog
from repro.core.clock import VirtualClock
from repro.core.discovery import admissible_set, discover
from repro.core.failures import FailureCause, SessionError
from repro.core.paging import PagingWeights, page, risk
from repro.core.predictors import Predictors
from repro.core.sites import default_sites


@pytest.fixture()
def world():
    clock = VirtualClock()
    catalog = default_catalog()
    sites = default_sites(clock, tuple(catalog._entries.keys()))
    analytics = Analytics(clock)
    predictors = Predictors(analytics)
    return clock, catalog, sites, analytics, predictors


class TestDiscovery:
    def test_candidates_annotated_and_sorted(self, world):
        clock, catalog, sites, analytics, predictors = world
        cands = discover(default_asp(), catalog, sites, predictors, "zone-a")
        adm = [c for c in cands if c.admissible]
        assert adm, "no admissible binding"
        slacks = [c.slack for c in cands]
        assert slacks == sorted(slacks, reverse=True)
        for c in adm:
            assert c.prediction.t_ff_ms > 0 and c.prediction.l99_ms > 0

    def test_sovereignty_hard_filter(self, world):
        clock, catalog, sites, analytics, predictors = world
        asp = dataclasses.replace(default_asp(), allowed_regions=("mars",))
        cands = discover(asp, catalog, sites, predictors, "zone-a")
        assert all(not c.admissible for c in cands)
        assert all(c.exclusion_reason == "sovereignty" for c in cands)
        with pytest.raises(SessionError) as ei:
            admissible_set(cands)
        assert ei.value.cause is FailureCause.NO_FEASIBLE_BINDING

    def test_negative_slack_excluded(self, world):
        clock, catalog, sites, analytics, predictors = world
        o = default_asp().objectives
        tight = dataclasses.replace(
            default_asp(),
            objectives=dataclasses.replace(o, ttfb_ms=0.001, p95_ms=0.002,
                                           p99_ms=0.002, t_max_ms=1.0))
        cands = discover(tight, catalog, sites, predictors, "zone-a")
        assert all(not c.admissible for c in cands
                   if c.exclusion_reason == "negative-slack"
                   or c.admissible is False)

    def test_a1_deny_list_respected(self, world):
        clock, catalog, sites, analytics, predictors = world
        analytics.deny_site("edge-a")
        cands = discover(default_asp(), catalog, sites, predictors, "zone-a",
                         analytics=analytics)
        assert all(c.site_id != "edge-a" for c in cands if c.admissible)

    def test_tier_filter(self, world):
        clock, catalog, sites, analytics, predictors = world
        asp = default_asp(tier=QualityTier.PREMIUM)
        cands = discover(asp, catalog, sites, predictors, "zone-a")
        for c in cands:
            if c.admissible:
                assert c.model.tier >= QualityTier.PREMIUM


class TestPaging:
    def test_picks_min_risk(self, world):
        clock, catalog, sites, analytics, predictors = world
        asp = default_asp()
        cands = discover(asp, catalog, sites, predictors, "zone-a")
        chosen = page(asp, cands)
        w = PagingWeights(w3=0.25)
        adm = [c for c in cands if c.admissible]
        assert risk(chosen, w) == min(risk(c, w) for c in adm)

    def test_exclusion_for_migration(self, world):
        clock, catalog, sites, analytics, predictors = world
        asp = default_asp()
        cands = discover(asp, catalog, sites, predictors, "zone-a")
        first = page(asp, cands)
        second = page(asp, cands, exclude_sites=(first.site_id,))
        assert second.site_id != first.site_id

    def test_mobility_weights_migration_risk(self, world):
        """A vehicular ASP should prefer anchors with lower migration risk
        (central) relative to a static ASP, all else equal."""
        clock, catalog, sites, analytics, predictors = world
        static = default_asp(mobility=MobilityClass.STATIC)
        vehic = default_asp(mobility=MobilityClass.VEHICULAR)
        c_static = page(static, discover(static, catalog, sites, predictors,
                                         "zone-a"))
        c_vehic = page(vehic, discover(vehic, catalog, sites, predictors,
                                       "zone-a"))
        kinds = {"edge": 0, "regional": 1, "central": 2}
        assert kinds[sites[c_vehic.site_id].spec.kind] >= \
            kinds[sites[c_static.site_id].spec.kind]

    @pytest.mark.parametrize("model", ["edge-tiny", "mamba2-1.3b",
                                       "minitron-8b"])
    def test_hinted_asp_binds_only_its_model(self, world, model):
        """A fallback ladder is the only admissible degradation path: a
        hinted ASP never pages onto a model it did not name."""
        clock, catalog, sites, analytics, predictors = world
        asp = default_asp(model, tier=catalog.get(model).tier)
        cands = discover(asp, catalog, sites, predictors, "zone-a")
        assert {c.model.model_id for c in cands} == {model}
        assert page(asp, cands).model.model_id == model
