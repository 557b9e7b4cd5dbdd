"""The records describe the repository that exists.

`BENCHMARK.json` is the benchmark; `PERF.md` is the builders' account of
it and every later session's way in.  A cell, metric or configuration the
benchmark declares and `PERF.md` never names is one nobody can look up;
a per-layer metric whose `layer` is no row of section 3's table has no
module to be traced to.  The documents, in turn, may only point at files
that are there: a deletion shows up here as the pointers it left behind.
"""

import functools
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return f.read()


BENCHMARK = json.loads(_read("BENCHMARK.json"))
DECLARED = [(kind, entry["name"], entry.get("layer"))
            for kind in ("workloads", "configs", "end_to_end", "per_layer")
            for entry in BENCHMARK[kind]]


def _layers_table(perf):
    """First column of the first table under PERF.md's '## 3. Layers'."""
    section = perf.split("\n## 3.", 1)[1].split("\n## 4.", 1)[0]
    lines = section.splitlines()
    start = next(i for i, line in enumerate(lines) if line.startswith("| "))
    rows = []
    for line in lines[start + 2:]:      # past the header and its rule
        if not line.startswith("| "):
            break
        rows.append(line.split("|")[1].strip())
    return set(rows)


def _named(name, text):
    """`a.b` may be written whole, or as `a.<first>` / `.b` in a list of
    suffixes, the way PERF.md writes `round_idle_ms.prepare` / `.fetch`."""
    if f"`{name}`" in text:
        return True
    stem, dot, suffix = name.partition(".")
    return bool(dot) and f"`{stem}." in text and f"`.{suffix}`" in text


@pytest.mark.parametrize("kind, name, layer", DECLARED,
                         ids=[f"{k}:{n}" for k, n, _ in DECLARED])
def test_perf_md_names_what_the_benchmark_declares(kind, name, layer):
    perf = _read("PERF.md")
    assert _named(name, perf), f"PERF.md never names {kind} `{name}`"
    if kind == "per_layer":
        assert layer in _layers_table(perf), (
            f"`{name}` is of layer {layer!r}, which is no row of PERF.md "
            f"section 3's table")


# -- the documents point at files that exist ---------------------------------

DOCUMENTS = ("README.md", "docs/api.md", "docs/benchmarks.md",
             "docs/elastic.md", "docs/migration.md")
ROOTS = ("", "horovod_tpu", "benchmarks", "tests", "docs", "examples")
SUFFIXES = ("py", "md", "json", "jsonl", "cc", "h", "sh", "toml", "ini",
            "csv", "txt")
_PATH = re.compile(r"`([\w./-]+\.(?:%s))(?:::?[\w.:\[\]-]+)?`"
                   % "|".join(SUFFIXES))
_SKIP_DIRS = {".git", "__pycache__", ".jax_cache", "chiprun_out",
              ".chip_tmp", ".bench_trace"}


@functools.cache
def _tree_names():
    names = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        names.update(files)
    return names


def _mentioned_paths(document):
    """Back-ticked tokens that look like a file of this repo.  A token
    with a leading slash is a URL path or a path on some machine.  README's 'Reference component'
    column holds the upstream project's file names, not ours."""
    for line in _read(document).splitlines():
        if document == "README.md" and line.startswith("| "):
            line = "|".join(line.split("|")[2:])
        for path in _PATH.findall(line):
            if not path.startswith("/"):
                yield path


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_points_at_files_that_exist(document):
    names = _tree_names()
    missing = sorted({
        path for path in _mentioned_paths(document)
        if not any(os.path.exists(os.path.join(REPO, root, path))
                   for root in ROOTS)
        and not ("/" not in path and path in names)})
    assert not missing, f"{document} points at files that are gone: " \
                        f"{missing}"
