"""Disaggregated serving, soak tier: the seeded two-pool campaigns (corrupt
KV chunks mid-handoff x prefill straggler x scheduled pool collapse) and
their replays, at last-page and at FIRST-page (pipelined) admission. A file
of their own: a campaign is minutes of interpreted serving and a file is
one xdist worker's job (split from test_disagg.py and
test_ranged_engine.py; the long sets are marked soak)."""

from __future__ import annotations

import pytest


@pytest.mark.chaos
def test_disagg_soak_campaign_quick_and_replay():
    """The chaos-matrix disagg soak cell: one seeded two-pool campaign
    (burst traffic × corrupt KV chunks mid-handoff × prefill straggler)
    passes every invariant and replays bit-identically from its seed."""
    from triton_dist_tpu.resilience import soak

    spec = soak.SoakSpec.disagg(seed=1, n_requests=10)
    res = soak.run_campaign(spec)
    assert res.ok, (res.failures, res.error)
    again = soak.run_campaign(spec)
    assert again.fingerprint == res.fingerprint


@pytest.mark.chaos
def test_disagg_soak_collapse_campaign():
    """The scheduled-pool-collapse composition (every third seed): the
    campaign must actually collapse and still satisfy every invariant."""
    from triton_dist_tpu.resilience import soak

    spec = soak.SoakSpec.disagg(seed=0, n_requests=10)
    assert spec.collapse_at_step > 0
    res = soak.run_campaign(spec)
    assert res.ok, (res.failures, res.error)
    assert res.snapshot["engine"]["collapsed"]


@pytest.mark.soak
def test_disagg_soak_campaign_set():
    """The full ISSUE 13 disagg set (5 seeds — what scripts/chaos_soak.py
    runs); soak marker ⇒ slow, never rides tier-1."""
    from triton_dist_tpu.resilience import soak

    for seed in range(200, 205):
        res = soak.run_campaign(soak.SoakSpec.disagg(seed=seed))
        assert res.ok, (seed, res.failures, res.error)


@pytest.mark.chaos
def test_pipelined_disagg_campaign_quick_and_replay():
    """The chaos-matrix pipelined-disagg cell: corrupt KV chunks injected
    mid-handoff while the decode pool admits at FIRST-page-landed — the
    guard ladder must attribute and recover (zero lost requests, every
    invariant green) and the campaign replays bit-identically."""
    from triton_dist_tpu.resilience import soak

    spec = soak.SoakSpec.disagg(seed=1, n_requests=10, pipelined_handoff=True)
    res = soak.run_campaign(spec)
    assert res.ok, (res.failures, res.error)
    again = soak.run_campaign(spec)
    assert again.fingerprint == res.fingerprint


@pytest.mark.chaos
@pytest.mark.slow
def test_pipelined_disagg_collapse_campaign():
    """The scheduled-pool-collapse composition under pipelined admission
    (every third seed): the topology collapses to unified mid-campaign
    with zero lost requests at the earlier admission gate."""
    from triton_dist_tpu.resilience import soak

    spec = soak.SoakSpec.disagg(seed=0, pipelined_handoff=True)
    assert spec.collapse_at_step > 0
    res = soak.run_campaign(spec)
    assert res.ok, (res.failures, res.error)
    assert res.snapshot["engine"]["collapsed"]


def test_soak_spec_pipelined_validation():
    """pipelined_handoff needs the disagg topology to gate."""
    from triton_dist_tpu.resilience import soak

    with pytest.raises(ValueError, match="pipelined_handoff"):
        soak.SoakSpec(seed=0, pipelined_handoff=True).validate()
