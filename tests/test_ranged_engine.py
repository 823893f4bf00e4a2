"""Ranged prefill, serving tier (split from test_ranged_prefill.py, see its
docstring): engine byte-identity and the long-prompt traffic stream (the
prefill work charge is test_prefill_work.py, pipelined disagg admission
test_pipelined_admission.py, its chaos campaign test_disagg_soak.py)."""

import pytest

from triton_dist_tpu.models.prefix_cache import PrefixCacheConfig

from ranged_helpers import _mk, _serve, bt_prompts, model1


@pytest.fixture(scope="module")
def streams(mesh2, model1, bt_prompts):
    """ONE serve an arm (the cold token-fed engine, px + prefill, chunked)
    of the same four requests: each prompt greedy and seeded-sampled, so
    that the second reader of a prompt hits the first's pages and samples;
    ``arm -> {uid: tokens}``."""
    from triton_dist_tpu.serving.engine import ServingConfig

    p1, p2 = bt_prompts
    sampled = dict(temperature=0.8, seed=5)
    arms = dict(
        cold={},
        px=dict(serving=ServingConfig(prefix_cache=PrefixCacheConfig()),
                page_size=4, prefill=True),
        chunked=dict(serving=ServingConfig(prefill_chunk_tokens=3),
                     prefill=True),
    )
    out = {}
    for arm, kw in arms.items():
        eng = _serve(model1, mesh2, [
            _mk("a", p1), _mk("b", p1, **sampled), _mk("c", p2),
            _mk("d", p2, **sampled)], **kw)
        out[arm] = {u: eng.results[u].tokens for u in "abcd"}
    return out


@pytest.mark.parametrize("arm", ["px", "chunked"])
def test_engine_px_prefill_byte_identity(streams, arm):
    """Engine tier: the px+prefill arm and the chunked arm produce the
    cold engine's exact token streams — greedy AND seeded-sampled (the
    sampled stream of a prompt is not its greedy one, so both are held)."""
    assert streams[arm] == streams["cold"]
    assert streams["cold"]["a"] != streams["cold"]["b"]


def test_traffic_long_prompt_stream():
    """The long-prompt traffic stream (ISSUE 18): an unset spec keeps its
    historical fingerprint byte-identically; an armed spec replaces ONLY
    the long prompts (non-long requests keep exact times and tokens);
    replay is byte-stable; the prefix pool composes (prepend happens
    after replacement); validation is loud."""
    from triton_dist_tpu.serving.traffic import (
        TrafficSpec, generate_trace, trace_fingerprint,
    )

    base = dict(
        rate_rps=4.0, n_requests=24, prompt_len=("uniform", 2, 6),
        output_len=("fixed", 4), vocab=32, seed=11,
    )
    plain = generate_trace(TrafficSpec(**base))
    # unset long-prompt fields = the field-less historical trace
    assert trace_fingerprint(plain) == trace_fingerprint(
        generate_trace(TrafficSpec(**base))
    )
    armed_spec = TrafficSpec(
        **base, long_prompt_frac=0.3, long_prompt_len=("fixed", 20)
    )
    armed = generate_trace(armed_spec)
    assert trace_fingerprint(armed) == trace_fingerprint(
        generate_trace(armed_spec)
    )
    n_long = 0
    for a, b in zip(plain, armed):
        assert a.t_s == b.t_s
        if len(b.request.prompt) == 20:
            n_long += 1
        else:
            assert a.request.prompt == b.request.prompt
    assert 0 < n_long < len(plain)
    # prefix prepend composes AFTER long replacement: armed long prompts
    # under a prefix pool are prefix + 20 tokens
    pxspec = TrafficSpec(
        **base, long_prompt_frac=0.3, long_prompt_len=("fixed", 20),
        prefix_pool=1, prefix_len=("fixed", 4), prefix_share=1.0,
    )
    pxtrace = generate_trace(pxspec)
    for a, b in zip(armed, pxtrace):
        assert b.request.prompt[4:] == a.request.prompt
    with pytest.raises(ValueError, match="long_prompt_len"):
        TrafficSpec(**base, long_prompt_frac=0.5).validate()
    with pytest.raises(ValueError, match="long_prompt_frac"):
        TrafficSpec(**base, long_prompt_len=("fixed", 20)).validate()
