"""The plan families' contract, written once: what every family served
through its own plan (``own_passes``: models/mla_moe.py, window_moe.py,
ssm_hybrid.py and their second blocks) has to show at toy widths on the
CPU against its plain reference, and AT WHAT SIZE. A family is a
``Family`` descriptor in a file of its own (test_mla_moe.py,
test_sparse_mla_moe.py, test_window_moe.py, test_prerouted_moe.py,
test_ssm_hybrid.py, test_retention.py), which imports the fixtures and the cases it takes
from here and keeps beside the descriptor what only that family has (its
kernels against their twins, its plan and parameter counts). So a family
stays ONE file and one xdist worker's job (``--dist loadfile``), its
programs are built once, and a new family is a descriptor, not a copy of
the last family's file (tests/test_docs_refs.py holds that).

The size is the budget (tests/conftest.py, runtime budget): a family file
may cost 120 s as one of six processes. What a case costs is interpreted
kernel calls (~44 ms each, so a toy decode round is 0.3 s a layer) and one
trace + compile a distinct static configuration, so:

- the toy has each attention kind and each MLP kind of its plan once, and
  a kind twice only where two layers must not meet in one pool;
- ``served`` is ONE batcher run: the batcher cases, the sound case of a
  planted fault and the re-admitted slot all read it, and its answers are
  just long enough to wrap a ring as often as the case's name says;
- a full forward runs at the lengths that take different paths;
- the shares of the bank run the one expert layer they cut."""

import contextlib
import dataclasses
import os
import sys
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.models import ContinuousBatcher, Request, gated_experts

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "perfbench")
if PERFBENCH not in sys.path:
    sys.path.insert(0, PERFBENCH)
from harness import cells, correct  # noqa: E402

from scope_helpers import check_pass  # noqa: E402

# float32 everywhere: what is left is the order of the sums
TOL = dict(rtol=2e-4, atol=2e-4)
# the limits a toy float32 run passes with room (its gaps are rounding:
# every served token is the reference's best or ties it) and anything
# wrong breaks
LIMITS = dict(max_gap=1e-3, mean_gap=1e-4)

# tests/test_*.py that load a module of perfbench/programs and are NOT a
# family's file, each with its reason (tests/test_docs_refs.py: any other
# such file takes its cases from here)
NOT_A_FAMILY = {
    "test_chip_compile.py": "compiles the committed cells' own programs at "
                            "their real size for a described chip",
    "test_live_prefix.py": "traces the real cells' sorted-row passes; the "
                           "pass itself runs on layers it builds",
}


@dataclasses.dataclass(frozen=True)
class Family:
    """One plan family at toy size. ``layer(ref, x, w, li, control, block)``
    is the reference's layer as that reference spells it; every other
    callable is named where the case that calls it is."""

    program: str                 # perfbench/programs/<program>.py
    reference: str               # perfbench/references/<reference>.py
    model: Any                   # the program's module under models/
    toy: dict                    # the toy configuration, "sizes" and all
    spec: type                   # the cache spec class its batcher builds
    layer: Callable
    name: str = ""               # which plan, where a file holds several
    # (ref, outer, tokens) -> the residual stream's start, where it is not
    # the plain lookup
    embed: Callable | None = None
    seed: int = 7
    tol: dict = dataclasses.field(default_factory=lambda: TOL)
    pack: Callable = lambda adapter, w, cfg, li: adapter.pack_layer(w, cfg)
    tiled: bool = False          # MATERIALIZED_UP_TO = 0 for the file
    # served: name -> (prompt, new[, the context it must pass]); slots are
    # fewer than cases, so the last cases land on slots that served before
    cases: dict = dataclasses.field(default_factory=dict)
    block: int | None = None     # the reference's query block, where it has one
    # full forward: id -> (length, MATERIALIZED_UP_TO or None for as it is)
    forward: dict = dataclasses.field(default_factory=dict)
    admissions: tuple = ()       # (slot, length, bucket)
    pools: dict = dataclasses.field(default_factory=dict)   # pool -> its table
    admitted: Callable = lambda counters, bucket: None      # its counters
    lowered: tuple = ()          # (bucket, the sorted rows' leading sizes)
    scopes: frozenset = frozenset()
    admission_scopes: frozenset = frozenset()               # beside `scopes`
    shares: int = 0              # shares of the bank of layer 1
    uncut: Callable | None = None        # (ref, x, m, w) -> the whole layer
    prerouted: bool = False      # the router reads other rows than the experts
    refused: tuple = ()          # names of REFUSALS
    refusal_says: tuple = ()     # one of these is in every refusal
    # a STATE kind's (a slot holds a state after its last position, not
    # rows): the pool a step must move, and the tolerance of a context of
    # a few tokens where it is not the family's
    state_pool: str | None = None
    token_fed_tol: dict | None = None
    # the engine run: requests (prompt, new), ServingEngine keywords,
    # `rebuild_after` steps (then the tokens are held to a plain batcher's),
    # `share(cfg, params)` where a share of the model is served, and
    # `check(cfg, params, by_name, requests)` for its spans
    engine: dict = dataclasses.field(default_factory=dict)

    @property
    def sizes(self) -> dict:
        return self.toy["sizes"]

    @property
    def s_max(self) -> int:
        return self.toy["engine"]["s_max"]

    @property
    def page(self) -> int:
        return self.toy["engine"]["page"]

    def make_spec(self, **kw):
        return self.spec(self.s_max, self.page, static_table=True, **kw)

    def n_moe(self, cfg) -> int:
        return sum("moe" in (kind if isinstance(kind, tuple) else (kind,))
                   for kind in self.model.layer_plan(cfg))


def sized(toy: dict) -> dict:
    """``toy`` with the ``sizes`` a reference reads."""
    return dict(toy, sizes={k: toy[k] for k in cells.SIZE_KEYS})


# -- fixtures: a family's module imports them ------------------------------------

# A file may hold SEVERAL plans of one family (``FAMILIES``, each with a
# ``name``): its tests then run a plan each (``pytest_generate_tests``).
# The fixtures stay a module's; what is costly to build is kept a plan
# (``_once``), so the order the plans' tests come in costs nothing.
_BUILT: dict = {}


def _once(request, family, what: str, build):
    key = (request.module.__name__, family.name, what)
    if key not in _BUILT:
        _BUILT[key] = build()
    return _BUILT[key]


def plans(module) -> tuple:
    return getattr(module, "FAMILIES", None) or (module.FAMILY,)


def only_for(*names):
    """A test of a file with several plans that is one plan's (a file's
    own test that names none is its FIRST plan's)."""
    def mark(fn):
        fn.families = names
        return fn
    return mark


@pytest.fixture(scope="module")
def family(request) -> Family:
    return getattr(request, "param", None) or plans(request.module)[0]


@pytest.fixture(scope="module", autouse=True)
def tiled_kernels_at_toy_buckets(family):
    """Where ``tiled``: the toy buckets are far under
    ``MATERIALIZED_UP_TO``, and the file runs them through the tiled
    kernels, as a real run's long buckets run."""
    with pytest.MonkeyPatch.context() as patch:
        if family.tiled:
            patch.setattr(family.model, "MATERIALIZED_UP_TO", 0)
        yield


@pytest.fixture(scope="module")
def ref(family):
    mod = cells.load_module("references", family.reference)
    mod.configure(family.toy)
    yield mod
    mod.configure(family.toy)


@pytest.fixture(scope="module")
def adapter(family):
    return cells.load_module("programs", family.program)


@pytest.fixture(scope="module")
def toy(request, family, ref, adapter):
    """``(cfg, program params, plain layers, outer)`` from one seed."""
    def build():
        cfg = adapter.model_config(family.toy)
        key = ref.seed_key(family.seed)
        plain = [ref.layer_weights(key, li, family.sizes)
                 for li in range(family.toy["n_layers"])]
        outer = ref.outer_weights(key, family.sizes)
        params = dict(outer, layers=[family.pack(adapter, w, cfg, li)
                                     for li, w in enumerate(plain)])
        return cfg, params, plain, outer

    return _once(request, family, "toy", build)


class Recording(Request):
    """A request that keeps every logit row it was sampled from and then
    takes the best token: logits are compared, not tokens."""

    def sample(self, logits, rng):
        self.__dict__.setdefault("rows", []).append(np.array(logits))
        return int(np.argmax(logits))


@pytest.fixture(scope="module")
def served(request, family, toy):
    """Every case through ONE batcher (fewer slots than cases, so slots
    are re-used; ``temperature`` > 0 keeps every round plain): ``(requests
    by name, tokens by name)``."""
    def build():
        cfg, params, _, _ = toy
        batcher = make_batcher(family, cfg, params)
        assert isinstance(batcher.spec, family.spec)
        rng = np.random.default_rng(0)
        reqs = {name: Recording(prompt_of(rng, cfg, case[0]), case[1],
                                temperature=1.0, uid=name)
                for name, case in family.cases.items()}
        for r in reqs.values():
            batcher.submit(r)
        return reqs, dict(batcher.run())

    return _once(request, family, "served", build)


# -- what the cases and a family's own tests call --------------------------------

def _ref_logits(family, ref, plain, outer, tokens, control=False, block=None):
    """The reference's logits at every position of ``tokens [n, T]``: its
    equations as they stand, traced into ONE program (eagerly each of its
    operations is a compile of its own: four times the seconds, and the
    logits differ by rounding, 3e-6)."""
    def logits(plain, outer, tokens):
        x = (family.embed(ref, outer, tokens) if family.embed
             else outer["embed"][tokens].astype(jnp.float32))
        for li, w in enumerate(plain):
            x = family.layer(ref, x, w, li, control, block)
        n, t = tokens.shape
        return ref.head(x, outer, jnp.zeros(n, jnp.int32), t, family.sizes,
                        control)

    return np.asarray(jax.jit(logits)(plain, outer, jnp.asarray(tokens)))


def forward_logits(family, cfg, params, tokens):
    """The program's whole-sequence forward, as ONE program (eagerly every
    op of every layer is a compile of its own: five times the seconds)."""
    return jax.jit(lambda p, t: family.model.forward_logits(cfg, p, t))(
        params, tokens)


def one_device(cfg) -> Mesh:
    return Mesh(np.array(jax.devices()[:1]), (cfg.axis,))


def make_batcher(family, cfg, params, **kw):
    kw.setdefault("prefill", True)
    return ContinuousBatcher(cfg, params, one_device(cfg), s_max=family.s_max,
                             page_size=family.page, **kw)


def prompt_of(rng, cfg, n: int) -> list:
    return [int(t) for t in rng.integers(0, cfg.vocab, n)]


def sampled_rows_match(family, ref, toy, r, out):
    """Every logit row ``r`` was sampled from against the reference's full
    forward over the sequence it served."""
    _, _, plain, outer = toy
    assert len(out) == r.max_new_tokens == len(r.rows)
    seq = list(r.prompt) + list(out)
    seq = np.array([seq + [0] * (-len(seq) % (family.block or 1))])
    want = _ref_logits(family, ref, plain, outer, seq, block=family.block)[0]
    first = len(r.prompt) - 1
    np.testing.assert_allclose(
        np.stack(r.rows), want[first:first + len(out)], **family.tol)


def verdict(family, ref, plain, outer, prompt, out):
    """``correct.verdict``, the comparison that decides a cell's
    ``correct``, of tokens ``out`` served after ``prompt``."""
    seq = np.array([list(prompt) + list(out)])
    want = _ref_logits(family, ref, plain, outer, seq)
    first = len(prompt) - 1
    gap, _ = ref.gaps(jnp.asarray(want[:, first:first + len(out)]),
                      np.array([out]))
    return _verdict_of(gap)


def _verdict_of(gap):
    return correct.verdict(dict(
        max_gap=float(gap.max()), mean_gap=float(gap.mean()), failed=0,
        health_flips=0, tokens_compared=gap.size), LIMITS)


@contextlib.contextmanager
def recorded_spans():
    """The span ring on for the block; yields a function that gives the
    attributes of the spans so far, by span name."""
    from triton_dist_tpu import config as tdt_config, obs
    from triton_dist_tpu.obs import ObsConfig

    def by_name() -> dict:
        out = {}
        for sp in obs.spans():
            out.setdefault(sp.name, []).append(sp.attrs)
        return out

    before = tdt_config.get_config().obs
    tdt_config.update(obs=ObsConfig(spans=True))
    obs.reset()
    try:
        yield by_name
    finally:
        tdt_config.update(obs=before)
        obs.reset()


def serve_through_the_engine(family, cfg, params, reqs, rebuild_after=0, **kw):
    """``reqs`` through ``ServingEngine`` on a fake clock with the span
    ring on, rebuilt mid-flight after ``rebuild_after`` steps:
    ``(results by uid, span attributes by name, engine)``."""
    from triton_dist_tpu.resilience import retry
    from triton_dist_tpu.serving import ServingConfig, ServingEngine

    with recorded_spans() as by_name:
        clock = retry.FakeClock()
        with retry.clock_scope(clock):
            eng = ServingEngine(
                cfg, params, one_device(cfg), s_max=family.s_max,
                page_size=family.page, prefill=True, clock=clock,
                serving=ServingConfig(virtual_step_s=0.01), **kw)
            for r in reqs:
                eng.submit(r)
            if rebuild_after:
                for _ in range(rebuild_after):
                    eng._step_once()
                assert eng._batcher.rounds_ahead > 0
                eng._rebuild("test")
            done = eng.run_until_idle()
        return done, by_name(), eng


# -- an admission -----------------------------------------------------------------
# An admission is ``prefill_cache`` with a one-hot ``slot_mask``: it runs
# the rows it admits and writes that slot's cache and no other's; without
# a mask every slot's rows run (``generate``'s form).

_ADMISSIONS = {}     # the programs below: a bucket's is built once a file


def _admission_program(cfg, spec, s_max, bucket, masked: bool):
    """The family's prefill at ``bucket`` as the batcher's program calls
    it, jitted over a one-device mesh."""
    key = (cfg, type(spec), s_max, bucket, masked)
    if key not in _ADMISSIONS:
        pcfg = dataclasses.replace(cfg, seq=bucket)
        _ADMISSIONS[key] = jax.jit(jax.shard_map(
            lambda p, c, t, m, k: pcfg.prefill_cache(
                p, c, t.reshape(-1), spec, s_max, slot_mask=m, pick=k,
                interpret=True),
            mesh=one_device(cfg),
            in_specs=(cfg.param_specs(), spec.specs(cfg), P(),
                      P() if masked else None, P()),
            out_specs=(spec.specs(cfg), P(), P()), check_vma=False))
    return _ADMISSIONS[key]


def admit(family, cfg, params, cache, slot, prompt, bucket):
    """``prompt`` into ``slot`` of ``cache`` as an admission does it:
    ``(cache, last, counters)``. ``slot=None``: every slot gets the
    prompt, no mask (``generate``'s form)."""
    tokens = np.zeros((cfg.batch, bucket), np.int32)
    pick = np.zeros(cfg.batch, np.int32)
    tokens[slot, :len(prompt)] = prompt      # (None indexes every row)
    pick[slot] = len(prompt) - 1
    mask = None if slot is None else jnp.arange(cfg.batch) == slot
    return _admission_program(
        cfg, family.make_spec(), family.s_max, bucket, slot is not None)(
        params, cache, jnp.asarray(tokens), mask, jnp.asarray(pick))


def random_cache(cfg, spec, rng):
    """The spec's cache with every pool filled: what other slots hold."""
    return jax.tree.map(
        lambda x: x if x.dtype == jnp.int32
        else jnp.asarray(rng.standard_normal(x.shape), x.dtype),
        spec.init(cfg, 1))


def slot_rows(cache, pools: dict, slot: int) -> dict:
    """``pool name -> the pages of ``slot`` in it`` (every layer)."""
    return {name: np.asarray(cache[name])[:, np.asarray(cache[table][0][slot])]
            for name, table in pools.items()}


def check_admission(family, cfg, params, slot, length, bucket, seed=0):
    """An admission of a ``length``-token prompt into ``slot`` against the
    unmasked pass over every slot's prompt from the same cache: the other
    slots' pages bit-identical to what they held, the admitted slot's
    pages and logit row the whole-batch pass's (to the family's
    tolerance), ``last`` zero elsewhere, and the pass's counters one
    slot's. Returns the admission's counters."""
    b, spec, pools, tol = cfg.batch, family.make_spec(), family.pools, family.tol
    rng = np.random.default_rng(seed)
    tokens = np.zeros((b, bucket), np.int32)
    tokens[:, :length] = rng.integers(0, cfg.vocab, (b, length))
    before = random_cache(cfg, spec, rng)
    whole, whole_last, whole_n = _admission_program(
        cfg, spec, family.s_max, bucket, False)(
        params, before, jnp.asarray(tokens), None,
        jnp.full(b, length - 1, jnp.int32))
    # the batcher's form: one live row, zeros elsewhere
    after, last, counters = admit(family, cfg, params, before, slot,
                                  tokens[slot, :length], bucket)
    want = slot_rows(whole, pools, slot)
    for other in range(b):
        held, now = slot_rows(before, pools, other), slot_rows(after, pools, other)
        for name in pools:
            if other != slot:
                np.testing.assert_array_equal(now[name], held[name])
            else:
                assert not np.array_equal(now[name], held[name])
                np.testing.assert_allclose(now[name], want[name], **tol)
    last = np.asarray(last)
    np.testing.assert_allclose(last[slot], np.asarray(whole_last)[slot], **tol)
    assert not np.delete(last, slot, axis=0).any()
    # every row chooses topk experts in each expert layer, held here or not
    for n_slots, values in ((1, counters), (b, whole_n)):
        named = dict(zip(cfg.pass_counters, (int(v) for v in values)))
        assert (named["assignments"] + named.get("assignments_elsewhere", 0)
                == n_slots * bucket * cfg.topk * family.n_moe(cfg))
    return counters


def _pallas_operands(jaxpr) -> list:
    """Operand shapes of every ``pallas_call`` of a jaxpr, sub-jaxprs
    walked in order."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append([tuple(v.aval.shape) for v in eqn.invars])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_pallas_operands(sub))
    return out


def admission_kernel_operands(cfg, params, spec, s_max, bucket, batch: int):
    """Shapes of what the admission's Pallas kernels (the grouped GEMMs)
    read, with ``cfg.batch = batch``: traced, nothing runs."""
    cfg = dataclasses.replace(cfg, batch=batch)
    shapes = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    jaxpr = jax.make_jaxpr(_admission_program(cfg, spec, s_max, bucket, True))(
        shapes(params), jax.eval_shape(lambda: spec.init(cfg, 1)),
        i32(batch, bucket), jax.ShapeDtypeStruct((batch,), jnp.bool_),
        i32(batch))
    return _pallas_operands(jaxpr.jaxpr)


# -- what a cache kind refuses ---------------------------------------------------

def refusals(family, cfg, params) -> dict:
    """``name -> (words of the refusal, what asks for it)``."""
    from triton_dist_tpu.models.prefix_cache import PrefixCacheConfig
    from triton_dist_tpu.serving.disagg import DisaggServingEngine
    from triton_dist_tpu.serving.speculative import (
        SpecDecodeConfig, SpeculativeBatcher,
    )

    one = one_device(cfg)
    two = Mesh(np.array(jax.devices()[:2]), (cfg.axis,))
    spec = family.make_spec()
    kw = dict(s_max=family.s_max, page_size=family.page)
    return {
        "prefix cache": ("prefix_cache", lambda: ContinuousBatcher(
            cfg, params, one, prefill=True,
            prefix_cache=PrefixCacheConfig(), **kw)),
        "ranged prefill": ("ranged prefill", lambda: ContinuousBatcher(
            cfg, params, one, prefill=True, prefill_chunk_tokens=8, **kw)),
        "contiguous cache": ("contiguous cache", lambda: ContinuousBatcher(
            cfg, params, one, s_max=family.s_max)),
        "wider mesh": ("wider than one device", lambda: ContinuousBatcher(
            cfg, params, two, **kw)),
        "wider mesh, the spec": ("one-device shard", lambda: spec.init(cfg, 2)),
        "verify": ("speculative verify", lambda: spec.update_multi_and_attend()),
        "the dense step": ("walks its own plan",
                           lambda: spec.update_and_attend()),
        "speculative decoding": (
            "speculative decoding", lambda: SpeculativeBatcher(
                cfg, params, one, spec_decode=SpecDecodeConfig(), **kw)),
        "handoff": ("disaggregated handoff", lambda: DisaggServingEngine(
            cfg, params, two, **kw)),
        "scratch page": ("prefix cache", lambda: family.make_spec(
            extra_pages=1).init(cfg, 1)),
    }


# -- the cases: a family's module imports those it takes -------------------------

def pytest_generate_tests(metafunc):
    """The cases of this module's tests come from the importing module's
    ``FAMILY``. Where it holds ``FAMILIES``, a test of this module runs
    once a plan (those that have what the test ``needs``), a test of the
    file's own for the plans it names (:func:`only_for`; its first plan
    where it names none); the plans of a file give a test the same
    cases."""
    mine = metafunc.function.__module__ == __name__
    fams = plans(metafunc.module)
    if hasattr(metafunc.module, "FAMILIES") \
            and "family" in metafunc.fixturenames:
        names = getattr(metafunc.function, "families",
                        None if mine else (fams[0].name,))
        needs = getattr(metafunc.function, "needs", None)
        fams = [f for f in fams if (names is None or f.name in names)
                and (needs is None or getattr(f, needs))]
        metafunc.parametrize("family", fams, indirect=True, scope="module",
                             ids=[f.name for f in fams])
    if not mine:
        return
    for arg, of in (
            ("case", lambda f: sorted(f.cases)),
            ("length", lambda f: list(f.forward)),
            ("admission", lambda f: f.admissions),
            ("what", lambda f: f.refused),
            ("which", lambda f: ("step", "admission"))):
        if arg in metafunc.fixturenames:
            values = of(fams[0])
            assert all(of(f) == values for f in fams), (arg, fams)
            metafunc.parametrize(arg, values, ids=lambda v: (
                "-".join(map(str, v)) if isinstance(v, tuple) else str(v)))


def test_full_forward_matches_reference(family, toy, ref, length, monkeypatch):
    """The program's whole-sequence forward (its prefill attention in the
    form the length chooses, grouped GEMMs and all) against the
    reference's, at each length that takes another path."""
    cfg, params, plain, outer = toy
    n, up_to = family.forward[length]
    if up_to is not None:
        monkeypatch.setattr(family.model, "MATERIALIZED_UP_TO", up_to)
    tokens = jax.random.randint(jax.random.PRNGKey(n), (2, n), 0, cfg.vocab)
    got = forward_logits(family, cfg, params, tokens)
    np.testing.assert_allclose(
        np.asarray(got), _ref_logits(family, ref, plain, outer, tokens),
        **family.tol)


def test_batcher_prefill_then_decode_matches_reference(
        family, toy, ref, served, case):
    """Prefill into the family's pools, then decode steps, ragged
    positions, slots re-used: every logit row the batcher sampled from
    against the reference's full forward over the same sequence."""
    reqs, done = served
    sampled_rows_match(family, ref, toy, reqs[case], done[case])
    if len(family.cases[case]) > 2:     # the ring wrapped as often as named
        assert len(reqs[case].prompt) + len(done[case]) > family.cases[case][2]


def test_an_admission_runs_and_writes_the_admitted_slot_only(
        family, toy, admission):
    """A one-hot mask on a slot, a prompt shorter than its bucket, filling
    it or longer than a ring: the other slots' pages bit-identical, the
    admitted slot's rows and logit row the unmasked whole-batch pass's,
    and one slot's rows counted."""
    cfg, params, _, _ = toy
    slot, length, bucket = admission
    counters = check_admission(family, cfg, params, slot % cfg.batch, length,
                               bucket, seed=bucket + slot)
    family.admitted([int(v) for v in counters], bucket)


def test_a_backlog_is_admitted_a_slot_a_pass(family, toy):
    """The batcher fills a whole-batch pass's rows with the queued
    requests of its bucket (docs/serving.md "The admission's
    discipline"); a plan family's pass runs the ONE slot its mask names
    (``gated_experts.admitted_rows``: ``argmax`` of a mask that must be
    one-hot), so a backlog of one bucket on free slots is still a pass a
    request, each handed a one-hot mask, in slot order. The bucket's
    program is a stand-in that keeps what it was given: nothing is
    traced."""
    cfg, params, _, _ = toy
    batcher = make_batcher(family, cfg, params)
    assert cfg.own_passes and not batcher._fills_rows
    given = []

    def program(bucket):
        def run(params, cache, prompt, mask, pick):
            given.append((bucket, np.asarray(mask), np.asarray(pick)))
            return cache, jnp.zeros((cfg.batch, cfg.vocab))
        return run

    batcher._prefill_prog = program
    batcher._pass_stats = np.zeros(len(batcher._counters), np.int32)
    rng = np.random.default_rng(0)
    lens = [5 + i % 3 for i in range(cfg.batch + 1)]     # bucket 8, all
    for i, n in enumerate(lens):
        batcher.submit(Request(prompt_of(rng, cfg, n), 3, uid=i))
    batcher._admit()
    assert batcher.prefill_passes_total == len(given) == cfg.batch
    assert [r.uid for r in batcher.queue] == [cfg.batch]
    for slot, (bucket, mask, pick) in enumerate(given):
        assert bucket == 8 and mask.tolist() == [
            i == slot for i in range(cfg.batch)]
        assert pick[slot] == lens[slot] - 1 and pick.sum() == pick[slot]


def test_the_lowered_admission_does_not_grow_with_the_batch(family, toy):
    """The grouped GEMMs of an admission read the same operands at 2 slots
    and at 4: one slot's ``bucket x topk`` assignments, each expert padded
    to a 128-row block, walked a chunk of one block at a time
    (gated_experts._chunk_blocks at expert_ffn 32): a chunk's rows in, and
    the whole result the down GEMM writes into."""
    cfg, params, _, _ = toy
    bucket, rows = family.lowered
    two, four = (admission_kernel_operands(
        cfg, params, family.make_spec(), family.s_max, bucket, b)
        for b in (2, 4))
    assert len(two) == 2 * family.n_moe(cfg) and two == four   # 2 GEMMs a layer
    assert {s[0] for call in two for s in call if len(s) == 2} == set(rows)


def test_every_part_of_a_pass_says_which_part_it_is(family, toy, which):
    """The lowered step and admission carry every scope of the family's
    row of the table (docs/observability.md) and no other ``tdt.`` name,
    and every matrix product and kernel call lies under a part; only the
    step calls the decode kernel."""
    cfg, params, _, _ = toy
    row = set(family.scopes)
    if which == "admission":
        row |= family.admission_scopes
    check_pass(which, cfg, params, family.make_spec(), one_device(cfg),
               family.s_max, row, bucket=16)


def test_shares_of_the_bank_add_up_to_the_layer(family, toy, ref):
    """The guide's share test: one expert layer's MLP run once per share
    of the bank, the routed parts summed and what every chip computes
    alike (the shared expert) counted once, equals the uncut layer, which
    is the reference's; and every assignment is counted once."""
    cfg, params, plain, _ = toy
    p, w = params["layers"][1], plain[1]
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    m = jax.random.normal(k1, (24, cfg.hidden), jnp.float32)   # the experts'
    x = (jax.random.normal(k2, m.shape, jnp.float32)           # the router's
         if family.prerouted else m)

    def layer(c, bank):
        routing = (gated_experts.route_rows(c, x, bank, 8)
                   if family.prerouted else None)
        return gated_experts.moe_mlp(c, m, bank, 8, routing=routing)

    held = cfg.n_experts // family.shares

    def whole_and_shares(p):
        # the share that holds expert 0 adds the shared expert
        return [layer(cfg, p)] + [
            layer(dataclasses.replace(cfg, experts_held=(first, held)),
                  dict(p, we_gate_up=p["we_gate_up"][first:first + held],
                       we_down=p["we_down"][first:first + held]))
            for first in range(0, cfg.n_experts, held)]

    # ONE program: a program a share is nine compiles for the same sums
    (whole, stats), *parts = jax.jit(whole_and_shares)(p)
    want = family.uncut(ref, x, m, w)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), **family.tol)
    total = sum(y for y, _ in parts)
    hit = sum(int(st[1]) for _, st in parts)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), **family.tol)
    assert hit == int(stats[1]) == 24 * cfg.topk      # every assignment, once
    if family.prerouted:    # routed on its own rows the layer is another function
        own, _ = jax.jit(lambda p: gated_experts.moe_mlp(cfg, m, p, 8))(p)
        assert np.abs(np.asarray(own) - np.asarray(want)).max() \
            > 50 * family.tol["atol"]


test_shares_of_the_bank_add_up_to_the_layer.needs = "shares"


def test_the_lower_precision_control_is_far_outside_the_tolerances(
        family, toy, ref):
    """The reference as W8A8 int8: its logits differ from the reference's
    by far more than the tolerance, and the token it puts first breaks the
    toy limits, so the comparisons here would catch a lower precision."""
    cfg, _, plain, outer = toy
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 16), 0, cfg.vocab)
    want = _ref_logits(family, ref, plain, outer, tokens)
    low = _ref_logits(family, ref, plain, outer, tokens, control=True)
    assert np.abs(low - want).max() > 50 * family.tol["atol"]
    gap, _ = ref.gaps(jnp.asarray(want), low.argmax(-1))
    ok, _ = _verdict_of(gap)
    assert not ok


def test_what_the_kind_cannot_serve_is_refused_by_name(family, toy, what):
    cfg, params, _, _ = toy
    match, build = refusals(family, cfg, params)[what]
    with pytest.raises(NotImplementedError, match=match) as err:
        build()
    assert any(word in str(err.value) for word in family.refusal_says)


# -- the cases of a family whose slots hold a STATE (``state_pool``) ---------------

def test_token_fed_admission_matches_reference(family, toy, ref):
    """``prefill=False``: the prompt goes in a token a step from position
    0, where the step reads zeros for the state whatever the slot holds."""
    cfg, params, _, _ = toy
    batcher = make_batcher(family, cfg, params, prefill=False)
    rng = np.random.default_rng(5)
    reqs = [Recording(prompt_of(rng, cfg, n), 2, temperature=1.0, uid=i)
            for i, n in enumerate((3, 2, 4, 2))]      # the 4th re-uses a slot
    for r in reqs:
        batcher.submit(r)
    done = dict(batcher.run())
    held_to = dataclasses.replace(family, tol=family.token_fed_tol or family.tol)
    for r in reqs:
        sampled_rows_match(held_to, ref, toy, r, done[r.uid])


def _run_with_a_late_arrival(family, cfg, params, **kw):
    """Two requests decode on three slots; a third arrives after the third
    step, while a step may be out ahead."""
    rng = np.random.default_rng(4)
    b = make_batcher(family, cfg, params, **kw)
    for i, (n, new) in enumerate([(5, 7), (3, 6)]):
        b.submit(Request(prompt_of(rng, cfg, n), new, uid=i))
    for _ in range(3):
        b.step()
    b.submit(Request(prompt_of(rng, cfg, 6), 3, uid="late"))
    return dict(b.run(max_steps=200)), b


def test_a_step_sent_in_vain_serves_the_plain_rounds_tokens(family, toy):
    """Lookahead on: the late admission moves the cache under a step that
    has ALREADY advanced every live slot's state in the donated cache; the
    step runs again and every slot's tokens are the plain batcher's."""
    cfg, params, _, _ = toy
    want, plain = _run_with_a_late_arrival(family, cfg, params, lookahead=False)
    got, b = _run_with_a_late_arrival(family, cfg, params)
    assert b.ahead_discarded >= 1 and b.rounds_ahead > 0
    assert plain.rounds_ahead == 0 and b.rounds == plain.rounds
    assert got == want


def test_decode_step_twice_on_the_same_inputs_is_decode_step_once(family, toy):
    """The planted form: the same ``(tok, pos)`` through the batcher's own
    step program twice gives the same logits and, bit for bit, the same
    cache: the second run read the state the first read, not the state it
    wrote."""
    cfg, params, _, _ = toy
    b = make_batcher(family, cfg, params, lookahead=False)
    rng = np.random.default_rng(6)
    for i, n in enumerate((5, 9, 2)):
        b.submit(Request(prompt_of(rng, cfg, n), 12, uid=i))
    for _ in range(3):
        b.step()
    tok, pos = jnp.asarray(b.tok), jnp.asarray(b.pos)
    copy = lambda tree: jax.tree.map(jnp.copy, tree)
    before = copy(b.cache)
    logits1, once = b._step(b.params, copy(before), tok, pos)
    kept = copy(once)                       # the step donates its cache
    logits2, twice = b._step(b.params, once, tok, pos)
    np.testing.assert_array_equal(np.asarray(logits1), np.asarray(logits2))
    for name, leaf in twice.items():
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(kept[name]),
                                      err_msg=name)
    # and the step did move the state: the test would see a double advance
    assert not np.array_equal(np.asarray(before[family.state_pool]),
                              np.asarray(twice[family.state_pool]))


def test_engine_serves_it_and_the_spans_carry_the_counters(family, toy):
    """The toy configuration through ``ServingEngine``: the same entry,
    scheduler and spans as the dense family, with the family's counters on
    the round's and the admission's spans and its bytes on the intake's.
    Where the run is rebuilt mid-flight the in-flight requests are
    re-admitted by prefill (prompt + tokens so far) and every request's
    tokens are the plain batcher's."""
    from triton_dist_tpu.serving.engine import Finished

    cfg, params, _, _ = toy
    e = family.engine
    cfg, params = e.get("share", lambda c, p: (c, p))(cfg, params)
    rng = np.random.default_rng(1)
    prompts = [prompt_of(rng, cfg, n) for n, _ in e["requests"]]
    reqs = lambda: [Request(list(p), new, uid=f"u{i}") for i, (p, (_, new))
                    in enumerate(zip(prompts, e["requests"]))]
    done, by_name, eng = serve_through_the_engine(
        family, cfg, params, reqs(), e.get("rebuild_after", 0),
        **e.get("kw", {}))
    assert all(isinstance(done[r.uid], Finished) for r in reqs())
    if e.get("rebuild_after"):
        plain = make_batcher(family, cfg, params, lookahead=False)
        for r in reqs():
            plain.submit(r)
        assert {u: list(f.tokens) for u, f in done.items()} == dict(plain.run())
        assert eng.rebuilds == 1
    e["check"](cfg, params, by_name, e["requests"], eng)
