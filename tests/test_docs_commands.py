"""The documents name only what exists.

Every command the four documents show, in a fenced block, on an indented
line or in a code span that starts with one, is reduced to the things it
names: a ``python <file>``
must name a file of the checkout, a ``python -m <module>`` a module that
``importlib`` finds, a ``make <target>`` a target of the ``Makefile``, an
``hvd-*`` / ``hvdrun-tpu`` word a key of ``[project.scripts]``. One case per
distinct name; nothing is run.
"""

import importlib.util
import os
import re
import tomllib

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ("README.md", "examples/README.md", "docs/DESIGN.md",
        "benchmark/README.md")

_STARTS = re.compile(r"^(?:[A-Z_][A-Z0-9_]*=\S*\s+)*"
                     r"(?:python3?|make|hvd-[a-z-]+|hvdrun-tpu)\b")
_WORD = re.compile(r"[^\s`'\"()<>|;]+")


def _command_lines(text):
    """Lines of fenced blocks, and outside them the indented lines and code
    spans that start with a command."""
    lines = []
    for block in re.findall(r"^```[^\n]*\n(.*?)^```", text, re.S | re.M):
        lines += block.replace("\\\n", " ").splitlines()
    outside = re.sub(r"^```.*?^```", "", text, flags=re.S | re.M)
    spans = re.findall(r"`([^`\n]+)`", outside)
    spans += re.findall(r"^ {4,}(\S.*)$", outside, re.M)
    return lines + [span for span in spans if _STARTS.match(span)]


def _named(line):
    """The (kind, name) pairs one command line names."""
    words = _WORD.findall(line.split(" #")[0])
    for i, word in enumerate(words):
        rest = words[i + 1:]
        if word in ("python", "python3") and rest:
            if rest[0] == "-m" and len(rest) > 1:
                yield "module", rest[1]
            elif rest[0].endswith(".py"):
                yield "file", rest[0]
        elif word == "make" and (i == 0 or words[i - 1] == "&&"):
            for arg in rest:
                if arg == "&&":
                    break
                if re.fullmatch(r"[a-z][a-z0-9-]*", arg):
                    yield "target", arg
        elif re.fullmatch(r"hvd-[a-z-]+|hvdrun-tpu", word):
            yield "script", word


def _collect():
    named = {}
    for doc in DOCS:
        with open(os.path.join(REPO, doc), encoding="utf-8") as f:
            for line in _command_lines(f.read()):
                for pair in _named(line):
                    named.setdefault(pair, doc)
    return sorted(named.items())


NAMED = _collect()  # [((kind, name), the first document that names it)]


def _make_targets():
    with open(os.path.join(REPO, "Makefile"), encoding="utf-8") as f:
        return set(re.findall(r"^([a-z][a-z0-9-]*):", f.read(), re.M))


def _scripts():
    with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
        return set(tomllib.load(f)["project"]["scripts"])


@pytest.mark.parametrize(
    "kind,name,doc", [(k, n, d) for (k, n), d in NAMED],
    ids=[f"{k}:{n}" for (k, n), _ in NAMED])
def test_documents_name_only_what_exists(kind, name, doc):
    if kind == "file":
        # a path is written from the repo's root, or, in a directory's own
        # README, from beside it
        here = os.path.dirname(os.path.join(REPO, doc))
        assert any(os.path.isfile(os.path.join(d, name))
                   for d in (REPO, here)), f"{doc}: no file {name}"
    elif kind == "module":
        assert importlib.util.find_spec(name) is not None, \
            f"{doc}: no module {name}"
    elif kind == "target":
        assert name in _make_targets(), f"{doc}: no make target {name}"
    else:
        assert name in _scripts(), f"{doc}: no console script {name}"


def test_the_documents_show_commands():
    """The guard guards something: the front page names the benchmark that
    judges PRs and the `make` targets a builder is sent to."""
    named = {pair for pair, _ in NAMED}
    assert ("file", "benchmark/run.py") in named
    assert ("target", "soak") in named and ("script", "hvd-top") in named
