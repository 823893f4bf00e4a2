"""Radix-shared prefix cache, chaos tier: the quick shared-prefix soak campaign
and its replay (a file of its own: two campaigns are one xdist worker's
job; the other tiers are test_prefix_cache.py and
test_prefix_cache_chaos.py)."""

import jax
import pytest


@pytest.mark.chaos
def test_quick_shared_prefix_soak_campaign_green():
    """One shared-prefix soak campaign (burst traffic over Zipf shared
    prefixes × straggler × corruption × a poisoned shared page): every
    invariant holds and the seed replays bit-identically — the ISSUE 12
    composition cell (full set: scripts/chaos_soak.py). On the smallest
    world a straggler can be shrunk out of (two PEs: the same 50 steps, 4
    rebuilds, poison and struck chain as on four, in a third of the time;
    a chain that spans four PEs is test_prefix_cache_chaos.py's)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    from triton_dist_tpu.resilience import soak

    spec = soak.SoakSpec.shared_prefix(
        seed=101, n_requests=10, world=2, corrupt_pe=0)
    a = soak.run_campaign(spec)
    assert a.error is None, a.error
    assert a.ok, a.failures
    assert a.snapshot["requests"].get("poisoned", 0) >= 1
    assert a.snapshot["requests"].get("prefix_struck", 0) >= 1, (
        "the poison landed on a multi-reader chain (deferred injection)"
    )
    b = soak.run_campaign(spec)
    assert b.fingerprint == a.fingerprint and b.terminals == a.terminals
