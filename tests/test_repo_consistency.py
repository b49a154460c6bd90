"""The documents and the CI script name only files that are there.

A reader who trusts a document runs what it names; a CI leg that runs a
tool whose input was deleted checks nothing (the pre-chip regression
tripwire outlived the history file it compared against by seven PRs).
Plain file reads: no jax import, under a second.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md", "PERF.md", "BASELINE.md", "docs/API.md",
        "docs/ANALYSIS.md", "docs/DURABILITY.md", "docs/OBSERVABILITY.md"]

#: what a run writes and nobody commits, by who writes it, and the one
#: file of the reference project the docs cite by bare name.  A deleted
#: record has no place here.
GENERATED = {
    # PipeGraph.dump_postmortem's bundle (docs/OBSERVABILITY.md)
    "manifest.json", "stats.json", "health.json", "events.json",
    "preflight.json", "device.json", "jit.json", "sweep.json",
    "shard.json", "latency.json", "tenant.json", "roofline.json",
    "calibration.json", "durability.json", "reshard.json",
    "ir_audit.json",
    # PipeGraph.dump_stats / dump_trace / the recorder's event log, under log/
    "app_stats.json", "app_trace.json", "app_events.json",
    # /root/reference/tests/win_tests_gpu
    "test_win_fat_gpu_tb.cpp",
}

_PATH = re.compile(r"[A-Za-z0-9_./<>*{}-]+\.(?:py|json|md|sh|cpp)\b")
_TICKED = re.compile(r"`([^`\n]+)`")
_LINKED = re.compile(r"\]\(([^)\s#]+)(?:#[^)]*)?\)")


def _named_paths(text):
    """Repo paths a markdown text names: inside backticks, or as a link
    target."""
    out = set()
    for span in _TICKED.findall(text):
        out.update(_PATH.findall(span))
    for target in _LINKED.findall(text):
        if "://" not in target:
            out.update(_PATH.findall(target))
    return out


def _exists(name, doc_dir):
    """A named path exists if it resolves from the repo root, from the
    document's own directory, from ``windflow_tpu/`` (the docs name
    modules package-relative), or, for a bare file name, anywhere in
    the tree."""
    if any(c in name for c in "<>*{}"):
        return True     # a pattern or a placeholder, not a file
    name = name.lstrip("./")
    for base in (REPO, os.path.join(REPO, doc_dir),
                 os.path.join(REPO, "windflow_tpu")):
        if os.path.exists(os.path.join(base, name)):
            return True
    if "/" in name:
        return False
    skip = {".git", ".chip_checkout", "chiprun_out", ".jax_cache",
            "__pycache__", "log"}
    for _root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        if name in files:
            return True
    return False


def _foreign(name):
    """Absolute paths outside the repo that the docs cite (the
    reference project's tree, scratch files)."""
    return name.startswith(("/root/reference", "/opt/", "/tmp/"))


@pytest.mark.parametrize("doc", DOCS)
def test_docs_name_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()
    missing = sorted(
        n for n in _named_paths(text)
        if os.path.basename(n) not in GENERATED and not _foreign(n)
        and not _exists(n, os.path.dirname(doc)))
    assert not missing, f"{doc} names files that do not exist: {missing}"


def test_ci_script_runs_only_files_that_exist():
    with open(os.path.join(REPO, "ci", "run_tests.sh"),
              encoding="utf-8") as f:
        # comments may say what a leg used to do; only commands count
        code = "\n".join(line.split("#", 1)[0] for line in f)
    named = set(re.findall(r"\bpython3?\s+(?!-)(\S+\.py)\b", code))
    named.update(re.findall(r"\btests/\S+\.py\b", code))
    # `tools.verify_targets:factory` arguments name a module and a function
    for mod, fn in re.findall(r"\b(tools\.\w+):(\w+)", code):
        path = mod.replace(".", "/") + ".py"
        named.add(path)
        with open(os.path.join(REPO, path), encoding="utf-8") as f:
            assert re.search(rf"^def {fn}\(", f.read(), re.M), \
                f"ci/run_tests.sh names {mod}:{fn}, which is not defined"
    assert named, "the parser found no command in ci/run_tests.sh"
    missing = sorted(n for n in named
                     if not os.path.exists(os.path.join(REPO, n)))
    assert not missing, \
        f"ci/run_tests.sh runs files that do not exist: {missing}"
