"""The documents name nothing that is not in the tree.

README.md, docs/OPERATIONS.md and docs/DESIGN.md are what an operator
reads first; a deletion that leaves a `python scripts/<gone>.py` line, a
subcommand or an environment variable behind makes them describe another
repo. Three checks a document, all over its text alone:

* every repo path it names (under scripts/, perf_capture/, benchmark/,
  akka_allreduce_tpu/, tests/ or docs/, ending in .py, .json or /) exists;
* every ``akka_allreduce_tpu.cli <word>`` and `` `cli <word>` `` names a
  registered subcommand;
* every ``AATPU_*`` variable is read somewhere under akka_allreduce_tpu/
  or tests/.
"""

import functools
import os
import re

import pytest

from akka_allreduce_tpu import cli

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ("README.md", "docs/OPERATIONS.md", "docs/DESIGN.md")

_PATH = re.compile(
    r"(?<![\w./-])((?:scripts|perf_capture|benchmark|akka_allreduce_tpu"
    r"|tests|docs)/(?:[\w./-]*?(?:\.py|\.json|/))?)(?![\w/-])")
_SUBCOMMAND = re.compile(
    r"(?:akka_allreduce_tpu\.cli|`cli(?:\.py)?) +([a-z][a-z-]*)")
_VARIABLE = re.compile(r"\bAATPU_[A-Z0-9_]*[A-Z0-9]")


def text_of(doc):
    with open(os.path.join(ROOT, doc)) as f:
        # a name may wrap across a line inside backticks
        return re.sub(r"/\n\s*", "/", f.read())


@functools.lru_cache(maxsize=None)
def sources():
    out = []
    for top in ("akka_allreduce_tpu", "tests"):
        for d, _, files in os.walk(os.path.join(ROOT, top)):
            for name in files:
                if name.endswith((".py", ".cpp", ".h")) \
                        and name != os.path.basename(__file__):
                    with open(os.path.join(d, name)) as f:
                        out.append(f.read())
    return "\n".join(out)


def missing_paths(doc):
    return sorted({p for p in _PATH.findall(text_of(doc))
                   if not os.path.exists(os.path.join(ROOT, p))})


def unknown_subcommands(doc):
    return sorted(set(_SUBCOMMAND.findall(text_of(doc)))
                  - set(cli._COMMANDS))


def unread_variables(doc):
    return sorted(v for v in set(_VARIABLE.findall(text_of(doc)))
                  if v not in sources())


@pytest.mark.parametrize("doc", DOCS)
@pytest.mark.parametrize("check", (missing_paths, unknown_subcommands,
                                   unread_variables),
                         ids=lambda f: f.__name__)
def test_document_names_only_what_exists(doc, check):
    assert check(doc) == []


def test_the_checks_can_fail():
    """Each pattern finds the thing it is for, so an empty list above
    means 'nothing dangling' and not 'nothing matched'."""
    text = ("run `python scripts/bench_wire.py`, then `cli perfgate`\n"
            "or python -m akka_allreduce_tpu.cli stress with "
            "AATPU_BENCH_ITERS=3; rows in `perf_capture/`\n")
    assert _PATH.findall(text) == ["scripts/bench_wire.py",
                                   "perf_capture/"]
    assert _SUBCOMMAND.findall(text) == ["perfgate", "stress"]
    assert _VARIABLE.findall(text) == ["AATPU_BENCH_ITERS"]
