"""``chip_smoke.py`` without a chip: it must FAIL here, and a ``--rehearse``
walk through the same code — device check stubbed HERE, not by an option
of the script — must end in the contract's last line. Plus the one compile
cache helper, and the smoke's loudness: a fused kernel that cannot build
ends the run instead of being served by its XLA golden. Plus the teeth of
the four-chip comparison: the teacher-forcing request and its two limits."""

import importlib
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu.resilience import health

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    """chip_smoke as a module with the two chip-only checks stubbed, and
    the process state it sets (loud posture, cache placement) contained."""
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(
        mod, "require_chip",
        lambda n: {"platform": "tpu", "kind": "stub", "count": n},
    )
    monkeypatch.setattr(mod, "assert_kernels_lowered", lambda text, what: None)
    monkeypatch.setattr(tdt_config, "compile_cache_dir", lambda: str(tmp_path))
    was = tdt_config.get_config().fallback_to_xla
    health.reset()
    yield mod
    tdt_config.update(fallback_to_xla=was)
    health.reset()


def _last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_no_chip_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, SCRIPT, "--rehearse"], capture_output=True,
        text=True, timeout=300, env=env, cwd=REPO,
    )
    assert proc.returncode != 0, proc.stdout
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearse_ends_in_contract_line(smoke, capsys, chips):
    assert smoke.main(["--rehearse", "--chips", str(chips)]) == 0
    assert _last_line(capsys) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "stub", "count": chips},
    }
    # the smoke left the loud posture on and nothing was downgraded
    assert tdt_config.get_config().fallback_to_xla is False
    assert health.snapshot()["healthy"]


def test_forced_mosaic_error_ends_the_run(smoke, monkeypatch, capsys):
    # ops/__init__ re-exports functions that shadow the submodule names
    common = importlib.import_module("triton_dist_tpu.ops.common")
    fd = importlib.import_module("triton_dist_tpu.ops.flash_decode")

    def refuse(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel: forced")

    monkeypatch.setattr(fd, "_paged_flash_decode_fused", refuse)
    # an earlier test's cached step program must not serve this run
    monkeypatch.setattr(common, "_jit_cache", {})
    monkeypatch.setattr(common, "_wrapper_cache", {})
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        smoke.main(["--rehearse"])
    assert '"ok"' not in capsys.readouterr().out
    # loud means loud: the golden was not served in its place
    assert not any(
        k.endswith(":downgrade") for k in health.snapshot()["counters"]
    )


def test_forced_request_answers_with_the_given_token(smoke):
    """The four-chip comparison's seam: a forced request records how far
    the given token trails the engine's best logit, and the engine's own
    argmax, then answers with the given token whatever the logits say."""
    import numpy as np

    from triton_dist_tpu.models.decode import Request

    reqs = [Request([1, 2, 3], max_new_tokens=2, uid="a")]
    forced, scores = smoke.forced_requests(reqs, {"a": [5, 0]})
    assert forced[0].temperature > 0 and forced[0].prompt == [1, 2, 3]
    row = np.array([0.5, 2.0, 0.0, 0.0, 0.0, 1.25], np.float32)
    assert forced[0].sample(row, None) == 5
    assert forced[0].sample(row, None) == 0
    assert scores["a"] == [(0.75, 1), (1.5, 1)]


@pytest.mark.parametrize(
    "gap, exact, complaint",
    [
        pytest.param(0.3, 1.0, "trails the best logit", id="gap"),
        pytest.param(0.0, 0.5, "are the argmax", id="exact_share"),
    ],
)
def test_limits_refuse_a_disagreeing_engine(smoke, gap, exact, complaint):
    import numpy as np

    from triton_dist_tpu.models.decode import Request

    reqs = [Request([1], max_new_tokens=4, uid=f"r{i}") for i in range(2)]
    gaps = np.zeros((2, 4))
    gaps[1, 2] = gap
    hits = np.arange(8).reshape(2, 4) < 8 * exact
    with pytest.raises(AssertionError, match=complaint):
        smoke.hold_to_limits(gaps, hits, reqs, "fused", "the golden engine")
    smoke.hold_to_limits(np.zeros((2, 4)), np.ones((2, 4), bool), reqs, "a", "b")


@pytest.mark.parametrize("env_set", [True, False], ids=["set", "unset"])
def test_compile_cache_helper(monkeypatch, tmp_path, env_set):
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda key, val: updates.append((key, val))
    )
    fixed = os.path.join(REPO, ".jax_cache")
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert tdt_config.compile_cache_dir() == str(tmp_path)
        assert updates == []          # placed from outside: nothing set in code
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert tdt_config.compile_cache_dir() == fixed
        assert [v for _, v in updates] == [fixed]
