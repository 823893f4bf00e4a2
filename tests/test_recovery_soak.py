"""Recovery plane, soak tier: the elastic-ON fleet campaign (decode straggler
regrow x prefill-storm collapse / un-collapse x windowed replica kill /
resurrect) and its replay (split from test_recovery.py: a campaign is one
xdist worker's job; the three-seed set is marked soak)."""

from __future__ import annotations

import pytest


@pytest.mark.chaos
def test_recovery_soak_campaign_quick_and_replay():
    """The chaos-matrix recovery cell: the elastic-ON fleet campaign
    (decode straggler regrow × prefill-storm collapse/un-collapse ×
    windowed replica kill/resurrect) passes every invariant — strikes
    provably scoped, the dead replica back AND serving — and replays
    bit-identically from its seed."""
    from triton_dist_tpu.resilience import soak

    spec = soak.SoakSpec.fleet_recovery_spec(seed=0, n_requests=16)
    res = soak.run_campaign(spec)
    assert res.ok, (res.failures, res.error)
    hc = res.health.get("counters", {})
    assert hc.get("serving_fleet:replica_readmit", 0) >= 1
    assert hc.get("serving_pool_decode:pool_regrow", 0) >= 1
    assert hc.get("serving_disagg:pool_uncollapse", 0) >= 1
    assert res.snapshot["engine"]["dead"] == []
    assert res.snapshot["fleet"]["resurrections"] >= 1
    # every PE health family in the campaign is scope-qualified
    pe_fams = [key.rsplit(":", 1)[0] for key in hc
               if key.startswith("pe") and key[2:3].isdigit()]
    assert pe_fams and all("@" in fam for fam in pe_fams), pe_fams
    again = soak.run_campaign(spec)
    assert again.fingerprint == res.fingerprint


@pytest.mark.soak
def test_recovery_soak_campaign_set():
    """The full ISSUE 17 recovery set (3 seeds — what
    scripts/chaos_soak.py runs); soak marker ⇒ slow, never tier-1."""
    from triton_dist_tpu.resilience import soak

    for seed in range(3):
        res = soak.run_campaign(soak.SoakSpec.fleet_recovery_spec(seed=seed))
        assert res.ok, (seed, res.failures, res.error)
