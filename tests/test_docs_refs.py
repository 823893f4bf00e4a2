"""The documents name only what the tree holds.

One case a document: every repo path it names must exist. A path is a
token under ``scripts/``, ``tests/``, ``docs/``, ``triton_dist_tpu/``,
``perfbench/``, ``tutorials/`` or ``csrc/`` that has a file suffix or ends
in ``/``, or a bare name: a ``*.md``, a ``*.json`` in capitals (the repo's
records are; a lower-case one is some run's output), or a ``*.py`` that
the document runs (``python X.py``). The documents also cite the upstream
reference's module files by bare name, which is why a bare ``*.py`` in
prose is not held to this tree. A bare name is looked for at the root and
beside the document. Skipped by rule, never by list: glob and placeholder
patterns, absolute paths, and paths that ``.gitignore`` lists, by
directory or by pattern (a build's output, e.g. ``csrc/*.so``: there only
once some test has built it). Where a document names a test (``tests/test_x.py::test_y``) the
file has to define it. A deleted script that a README still tells its
reader to run fails here (PR 30 removed ~3.7 k lines of such)."""

import fnmatch
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent

DOCUMENTS = [
    "README.md",
    *sorted(p.relative_to(ROOT).as_posix() for p in ROOT.glob("docs/*.md")),
    "tutorials/README.md",
    ".claude/skills/verify/SKILL.md",
    "scripts/run_tier1.sh",
]

_TOKEN_SPLIT = re.compile(r"[\s`()\[\]\"',;=]+")
_ROOTED = re.compile(
    r"(?:scripts|tests|docs|triton_dist_tpu|perfbench|tutorials|csrc)"
    r"/[\w./-]*")
_BARE = re.compile(r"[\w-]+\.md|[A-Z_\d]+\.json")
_RUN = re.compile(r"[\w-]+\.py")
_PATTERN_CHARS = set("*?<>{}$")


def _ignored() -> tuple[set[str], list[str]]:
    """``.gitignore``'s directories, and its other lines as patterns."""
    lines = (ROOT / ".gitignore").read_text().split()
    return ({ln.strip("/") for ln in lines if ln.endswith("/")},
            [ln for ln in lines if not ln.endswith("/")])


def _named_paths(text: str):
    """(token, is_bare, test name or "") for every repo path `text` names."""
    before = ""
    for raw in _TOKEN_SPLIT.split(text):
        ran, before = before in ("python", "python3"), raw
        if _PATTERN_CHARS & set(raw) or raw.startswith(("/", "~", "http")):
            continue
        # `file.py::test_name`, `file.py:553`, `doc.md#anchor`, `file.py.`
        token, _, rest = raw.partition("::")
        token = re.split(r"[:#]", token, maxsplit=1)[0].rstrip(".")
        if token.startswith("./"):
            token = token[2:]
        test = re.match(r"\w*", rest).group()
        if _ROOTED.fullmatch(token):
            if token.endswith("/") or pathlib.PurePosixPath(token).suffix:
                yield token, False, test
        elif _BARE.fullmatch(token) or (ran and _RUN.fullmatch(token)):
            yield token, True, ""


def _missing(document: str) -> list[str]:
    ignored, built = _ignored()
    here = (ROOT / document).parent
    missing = set()
    for token, bare, test in _named_paths((ROOT / document).read_text()):
        if ignored & set(pathlib.PurePosixPath(token).parts) or any(
                fnmatch.fnmatch(token, pattern) for pattern in built):
            continue
        homes = (ROOT, here) if bare else (ROOT,)
        if not any((home / token).exists() for home in homes):
            missing.add(token)
        elif test and not re.search(
                rf"^def {test}\(", (ROOT / token).read_text(), re.M):
            missing.add(f"{token}::{test}")
    return sorted(missing)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document):
    assert (ROOT / document).is_file(), document
    assert _missing(document) == [], (
        f"{document} names paths the tree does not hold")


def test_reference_rules_catch_and_skip():
    """The rules themselves: what counts as a named path, what is skipped."""
    text = (
        "run `python scripts/gone.py --x`, `python bench.py` and "
        "[doc](docs/serving.md#bench), [rows](trends.md), `BASELINE.json`, "
        "see `tests/test_x.py::test_y`, `triton_dist_tpu/obs/`, "
        # skipped: package-relative, glob, placeholder, absolute, a run's
        # output, the reference's module in prose, a path with no suffix
        "`models/decode.py:553`, `BENCH_*.json`, `scripts/<name>.sh`, "
        "`/root/TESTS_LAST_RUN.json`, `census.json`, `PERF_LEDGER.jsonl`, "
        "`allgather.py:72-76`, `tutorials/01-08`."
    )
    assert sorted(_named_paths(text)) == [
        ("BASELINE.json", True, ""), ("bench.py", True, ""),
        ("docs/serving.md", False, ""), ("scripts/gone.py", False, ""),
        ("tests/test_x.py", False, "test_y"), ("trends.md", True, ""),
        ("triton_dist_tpu/obs/", False, ""),
    ]


def test_long_poles_are_test_files():
    import conftest

    assert conftest._LONG_POLES, "the ordering names no file"
    assert len(set(conftest._LONG_POLES)) == len(conftest._LONG_POLES)
    for name in conftest._LONG_POLES:
        assert (ROOT / "tests" / name).is_file(), name


def test_a_plan_familys_tests_are_a_descriptor_for_the_tier():
    """The rule that keeps a family's tests from being a copy of the last
    family's (tests/conftest.py, runtime budget): a test file that loads a
    module of ``perfbench/programs`` takes its cases from
    tests/family_tier.py and describes its family there (``FAMILY``), or
    is named in the tier's short list with its reason."""
    import family_tier

    tests = ROOT / "tests"
    assert all((tests / name).is_file() and why
               for name, why in family_tier.NOT_A_FAMILY.items())
    loads = re.compile(r"load_module\(\s*\"programs\"")
    families = []
    for path in sorted(tests.glob("test_*.py")):
        text = path.read_text()
        if path.name in family_tier.NOT_A_FAMILY:
            continue
        takes = re.search(r"^from family_tier import ", text, re.M)
        if loads.search(text):
            assert takes, f"{path.name} loads a program beside the tier"
        if takes:
            assert re.search(r"^FAMILY = Family\(", text, re.M), path.name
            assert not re.search(r"^def\s+_ref_logits\(", text, re.M), path.name
            families.append(path.name)
    assert len(families) >= 5, families


def test_known_failures_name_tests_that_exist():
    """The manifest the gate diffs against (scripts/diff_failures.py) names
    only tests the tree still defines: a deleted test cannot hide in it."""
    manifest = ROOT / "tests" / "known_failures.txt"
    for line in manifest.read_text().split("\n"):
        node = line.split("#", 1)[0].strip()
        if not node:
            continue
        path, _, rest = node.partition("::")
        func = rest.split("[", 1)[0]
        source = (ROOT / path).read_text()
        assert re.search(rf"^def {re.escape(func)}\(", source, re.M), node
