"""The documents against the tree: every repo path a document names in
backticks exists, and the `TDN_*` switches the package reads are the
ones the documents name. A document that sends its reader to a file,
a tool or a switch that is gone fails here, not in the reader's shell.
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, ROOT) for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
)
TOP_DIRS = ("tools", "tpu_dist_nn", "tests", "benchmark", "examples", "docs",
            "scenarios", "native", "config")
PACKAGE = os.path.join(ROOT, "tpu_dist_nn")
# Records and documents of the repo's root are written in capitals
# (`PERF.md`, `BENCHMARK.json`); a lower-case `m.json` is a reader's file.
_ROOT_RECORD = re.compile(r"^[A-Z][A-Z0-9_]*(_r\d+)?\.(md|json|jsonl)$")
_BARE_PY = re.compile(r"^\w+\.py$")
_PATH = re.compile(r"^[\w.\-]+(/[\w.\-]+)+/?$")
_SWITCH = re.compile(r"\bTDN_[A-Z0-9_]+\b")
# The reference's own sources (SURVEY.md), named beside what replaced them.
REFERENCE_FILES = {"grpc_node.py", "manual_nn.py", "run_grpc_fcnn.py",
                   "run_grpc_inference.py", "generate_mnist_pytorch.py"}


def _read(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
        return f.read()


def _basenames():
    """File names of the root and of everything under TOP_DIRS (not of
    git-ignored scratch beside them)."""
    names = set(os.listdir(ROOT))
    for top in TOP_DIRS:
        for _, _, files in os.walk(os.path.join(ROOT, top)):
            names.update(files)
    return names


def _named_paths(text):
    """Repo paths among a document's backticked words, each with the
    place it has to exist in."""
    for quoted in re.findall(r"`([^`\n]+)`", text):
        for word in quoted.split():
            word = word.split("::")[0].rstrip(".,;:)").lstrip("(")
            if any(c in word for c in "*<>{}$…"):
                continue  # a pattern or a placeholder, not a path
            if word in REFERENCE_FILES:
                continue
            if _ROOT_RECORD.match(word):
                yield word, [ROOT]
            elif _BARE_PY.match(word):
                yield word, None  # anywhere in the tree, by name
            elif _PATH.match(word):
                head = word.split("/")[0]
                if head in TOP_DIRS:
                    yield word, [ROOT]
                elif word.endswith(".py") and os.path.isdir(
                        os.path.join(PACKAGE, head)):
                    yield word, [PACKAGE]  # `serving/wire.py`


@pytest.mark.parametrize("doc", DOCS)
def test_every_repo_path_a_document_names_exists(doc):
    names = _basenames()
    missing = sorted({
        word for word, roots in _named_paths(_read(doc))
        if not (word in names if roots is None else any(
            os.path.exists(os.path.join(r, word)) for r in roots))
    })
    assert not missing, f"{doc} names paths that are not in the tree: {missing}"


def _switches_read(*tops):
    found = set()
    for top in tops:
        for path in glob.glob(os.path.join(ROOT, top, "**", "*.py"),
                              recursive=True):
            found.update(_SWITCH.findall(_read(os.path.relpath(path, ROOT))))
    return found


def _switches_documented():
    return set().union(*(_SWITCH.findall(_read(doc)) for doc in DOCS))


@pytest.mark.parametrize("switch", sorted(_switches_read("tpu_dist_nn")))
def test_every_switch_the_package_reads_is_documented(switch):
    assert switch in _switches_documented(), (
        f"{switch} is read under tpu_dist_nn/ and named in neither "
        "README.md nor docs/: add it to the README's table"
    )


def test_the_documents_name_no_switch_nothing_reads():
    # TDN_TEST_TPU is the tests' own (tests/conftest.py).
    stale = _switches_documented() - _switches_read("tpu_dist_nn", "tests")
    assert not stale, f"documented, read nowhere: {sorted(stale)}"
