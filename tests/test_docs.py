"""The documents name only what is in the tree.

A path a document puts in backticks is a promise that the file is there:
`README.md` once presented a benchmark nobody ran for thirty PRs.  Each
document is read for backticked tokens that look like paths of this tree,
and every one has to be the tail of a path a walk of the checkout finds
(git-ignored directories skipped) — the documents write
`engine/batching.py`, `traffic/long-prompt.json` and bare `moe_readers.py`
alike, so a token is matched by suffix, not against one root.
`ROADMAP.md` is left out: a session that runs no tests rewrites it.
"""

import functools
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ("README.md", "PERF.md", "DESIGN.md", "PARITY.md", "CONFIG.md",
             "METRICS.md")
FILE_ENDINGS = (".py", ".md", ".json", ".jsonl", ".sh", ".cc", ".npz")
# Named on purpose though absent: what a run leaves behind (git-ignored),
# the reference's own tree, a guide of the builder's (`workloads.md`), and
# files a sentence says are gone.
ABSENT_PREFIXES = ("src/", "/root/reference/", "chiprun_out/", ".jax_cache/",
                   "checkpoints/", "plots/", "plugins/profile/")
ABSENT_NAMES = {"benchmark_results.csv", "benchmark_per_query.csv",
                "final_results.csv", "config.json", "workloads.md"}
GONE_BEFORE = re.compile(r"\b(was|were|deleted|removed|gone|went|git show)\b"
                         r"[^`]{0,60}$")
GONE_AFTER = re.compile(r"^[^`.]{0,20}\b(was|were) (removed|deleted)\b")


def _ignored_dirs():
    with open(os.path.join(ROOT, ".gitignore"), encoding="utf-8") as f:
        lines = [ln.strip() for ln in f]
    return {ln.rstrip("/") for ln in lines if ln.endswith("/")} | {".git"}


@functools.lru_cache(maxsize=None)
def _tree():
    """Every file and directory of the checkout, relative, '/'-joined."""
    skip = _ignored_dirs()
    found = set()
    for here, dirs, files in os.walk(ROOT):
        rel = os.path.relpath(here, ROOT).replace(os.sep, "/")
        rel = "" if rel == "." else rel + "/"
        dirs[:] = [d for d in dirs
                   if d not in skip and (rel + d) not in skip]
        found.update(rel + d for d in dirs)
        found.update(rel + f for f in files)
    return frozenset(found)


@functools.lru_cache(maxsize=None)
def _directory_names():
    return frozenset(seg for path in _tree() for seg in path.split("/")[:-1])


def _is_tail(token):
    return any(t in _tree() or any(p.endswith("/" + t) for p in _tree())
               for t in (token, token + ".py"))


def _path_tokens(text):
    """(token, the text before its span, the text after) for every
    backticked word that looks like a path of this tree."""
    for m in re.finditer(r"`([^`\n]+)`", text):
        for word in m.group(1).split():
            if any(c in word for c in "<*…{}$=()[]|,'\"") \
                    or word.startswith(("/", "-", "http")):
                continue
            word = re.sub(r"(::[\w\[\]-]+)+$|(:\d+(-\d+)?)+$", "", word)
            word = word.rstrip("/.:;")
            if not word or word in ABSENT_NAMES \
                    or word.startswith(ABSENT_PREFIXES):
                continue
            around = text[max(0, m.start() - 80):m.start()], text[m.end():
                                                                   m.end() + 40]
            if word.endswith(FILE_ENDINGS):
                yield (word, *around)
            elif "/" in word and word.split("/")[0] in _directory_names():
                # `utils/roofline.decode_work`: a name inside a module
                head, _, last = word.rpartition("/")
                if "." in last:
                    word = f"{head}/{last.split('.')[0]}.py"
                yield (word, *around)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(ROOT, document), encoding="utf-8") as f:
        text = f.read()
    tokens = list(_path_tokens(text))
    assert tokens or document in ("CONFIG.md", "METRICS.md"), \
        f"{document}: the reader found no path at all"
    missing = sorted({tok for tok, before, after in tokens
                      if not _is_tail(tok)
                      and not GONE_BEFORE.search(before)
                      and not GONE_AFTER.search(after)})
    assert not missing, (
        f"{document} names paths that are not in the tree: {missing}")


def test_readme_names_the_benchmark_the_driver_runs():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        command = " ".join(json.load(f)["command"])
    with open(os.path.join(ROOT, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    assert "benchmark/run.py" in command
    assert command in readme and "BENCHMARK.json" in readme
    assert "bench.py" not in readme
