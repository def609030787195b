"""The repo's documents and sources name only what is there.

PR 45 removed the CPU-era bench, its ledger and the tooling around them.
``test_nothing_names_what_is_gone`` keeps the sources and the two living
documents from naming them again; ``test_a_document_names_no_path_that_is_missing``
holds every path a living document quotes to the files git would commit.
The histories (CHANGES.md, PERF.md, ROADMAP.md, SURVEY.md, BASELINE.*) are
not searched: they say what was.
"""

import functools
import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SEARCHED = ["anovos_tpu", "tools", "tests", "config", "examples", ".github", "__graft_entry__.py", "chip_smoke.py",
            "main.py", "run_demo.sh", "Dockerfile", "pyproject.toml", "README.md", "COMPONENTS.md"]

# case-sensitive: PERF_LEDGER.jsonl is the driver's file and stays
GONE = ["bench.py", "perf_ledger", "oocore_bench", "record_block_budget", "BENCH_LEDGER", "ANOVOS_PERF_LEDGER",
        "drift_device_args", "check_no_print"]


@functools.lru_cache(maxsize=None)
def _committed():
    """Every path git would commit (tracked, or new and not ignored), or, where
    this is no git checkout, every file on disk."""
    try:
        out = subprocess.run(["git", "ls-files", "--cached", "--others", "--exclude-standard"], cwd=REPO,
                             capture_output=True, text=True, check=True).stdout.splitlines()
    except (OSError, subprocess.CalledProcessError):
        out = [os.path.relpath(os.path.join(d, f), REPO) for d, dirs, files in os.walk(REPO) for f in files
               if ".git" not in d.split(os.sep)]
    return sorted(p for p in out if os.path.isfile(os.path.join(REPO, p)))


@functools.lru_cache(maxsize=None)
def _searched_text():
    me = os.path.relpath(os.path.abspath(__file__), REPO)
    texts = {}
    for path in _committed():
        if path == me or not any(path == s or path.startswith(s + "/") for s in SEARCHED):
            continue
        try:
            with open(os.path.join(REPO, path), encoding="utf-8") as f:
                texts[path] = f.read()
        except UnicodeDecodeError:
            pass  # a binary fixture names nothing
    return texts


@pytest.mark.parametrize("name", GONE)
def test_nothing_names_what_is_gone(name):
    hits = [f"{path}:{i}" for path, text in _searched_text().items() if name in text
            for i, line in enumerate(text.splitlines(), 1) if name in line]
    assert not hits, f"{name!r} was removed in PR 45 and is named in: {hits}"


SUFFIXES = (".py", ".yaml", ".json", ".md", ".sh")
PLACEHOLDER = re.compile(r"[*<>{}$…|\[\]=]|\.\.\.|/path/to/")
# files a run writes, named by the documents that describe a run's output
WRITTEN_BY_A_RUN = re.compile(r"^(obs/)?((run|quarantine|state)_manifest|trace(_\d+)?)\.json$")


@pytest.mark.parametrize("document", ["README.md", "COMPONENTS.md"])
def test_a_document_names_no_path_that_is_missing(document):
    files = _committed()
    dirs = {"/".join(p.split("/")[:i]) for p in files for i in range(1, p.count("/") + 1)}
    top = {d for d in dirs if "/" not in d}
    paths = ["/" + p for p in (*files, *sorted(dirs))]
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        tokens = re.findall(r"`([^`\n]+)`", f.read())
    missing = []
    for word in (w for token in tokens for w in token.split()):
        word = re.sub(r":\d+(-\d+)?$", "", word.split("::")[0].strip("(),;.")).rstrip(":/")
        if PLACEHOLDER.search(word) or WRITTEN_BY_A_RUN.match(word):
            continue
        if not (word.endswith(SUFFIXES) or ("/" in word and word.split("/")[0] in top)):
            continue
        # a tracked path, or the tail of one (a bare file name, a path inside the package)
        if not any(p.endswith("/" + word) for p in paths):
            missing.append(word)
    assert not missing, f"{document} names paths that git would not commit: {sorted(set(missing))}"
