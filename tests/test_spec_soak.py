"""Speculative serving, chaos tier: the seeded speculative soak campaign
and its replay (split from test_spec_serving.py in PR 23 — each campaign
is minutes of interpreted serving, and under ``--dist loadfile`` a file is
one worker's whole job)."""

import pytest

from triton_dist_tpu.resilience import soak


@pytest.mark.chaos
def test_quick_speculative_soak_green():
    """One speculative campaign (self-draft k=3 × persistent straggler ×
    draft corruption): speculation survives the full quarantine → shrink →
    replay → regrow arc, every injected draft corruption is rejected by
    the verify pass, and the streams match a clean plain reference byte
    for byte (check_spec_invariants). On the smallest world a straggler
    can be shrunk out of (two PEs: the same 24 steps, 12 speculative
    rounds, 2 rebuilds and 2 draft faults as on four, in a third of the
    time; the replay cell below keeps the 4-PE world, where the shrink has
    to skip the mesh of three)."""
    res = soak.run_campaign(soak.SoakSpec.speculative(
        seed=600, n_requests=6, world=2, corrupt_pe=0))
    assert res.error is None, res.error
    assert res.ok, res.failures
    assert res.rebuilds >= 1, "the straggler arc rebuilt mid-speculation"
    sp = res.snapshot.get("speculative") or {}
    assert sp.get("rounds", 0) > 0
    assert sp.get("draft_faults_injected") == res.spec.n_draft_corruptions
    assert sp.get("rollback_total", 0) >= res.spec.n_draft_corruptions


@pytest.mark.chaos
@pytest.mark.slow  # two full campaigns, ~7 min interpreted: chaos_matrix.sh runs it
def test_speculative_soak_replay_bit_identical():
    spec = soak.SoakSpec.speculative(seed=601)
    a, b = soak.run_campaign(spec), soak.run_campaign(spec)
    assert a.ok and b.ok, (a.failures, b.failures)
    assert a.fingerprint == b.fingerprint
    assert a.terminals == b.terminals
