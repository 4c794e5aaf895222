"""Doc-drift guards: tools/check_metric_docs.py keeps the metric catalog
in docs/observability.md in sync with the registered families, and every
path a living document names in backticks exists in the tree."""
import glob
import importlib.util
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the documents that describe today's tree; dated records (CHANGES.md,
# VERDICT.md, ADVICE.md, ROADMAP.md, SURVEY.md) may name what is gone
_LIVING_DOCS = ["README.md", "PERF.md", "COVERAGE.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
_PATH_ROOTS = ("mxnet_tpu/", "tools/", "tests/", "benchmark/", "docs/",
               "examples/")


def _named_paths(text):
    """Backticked tokens that start with a directory of the repo, cut to
    the path: `a/b.py:12`, `a/b.py:fn`, `a/b.py::test` and `a/b.py --opt`
    all name `a/b.py`.  Patterns (`*`, `<x>`, `{a,b}`) are skipped, and so
    are bare module names (`engine.py`), which the documents use freely."""
    for token in re.findall(r"`([^`\n]+)`", text):
        if not token.startswith(_PATH_ROOTS) or re.search(r"[*<{]", token):
            continue
        yield token.split()[0].split(":")[0]


@pytest.mark.parametrize("doc", _LIVING_DOCS)
def test_paths_named_in_documents_exist(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        named = sorted(set(_named_paths(f.read())))
    gone = [p for p in named if not os.path.exists(os.path.join(REPO, p))]
    assert not gone, "%s names paths that are not in the tree: %s" % (
        doc, gone)


def _load():
    spec = importlib.util.spec_from_file_location(
        "check_metric_docs_under_test",
        os.path.join(REPO, "tools", "check_metric_docs.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_repo_metric_docs_are_in_sync():
    # the real check the strict-lint CI job runs: every mxnet_* family
    # registered in the framework has a row in docs/observability.md
    mod = _load()
    assert mod.missing_families() == []
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "check_metric_docs.py")],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_registered_families_sees_known_call_sites():
    fams = _load().registered_families()
    # one per instrumented layer: engine, compile, kvstore, serve, memory
    for known in ("mxnet_engine_ops_pushed_total", "mxnet_compiles_total",
                  "mxnet_kvstore_rpc_seconds", "mxnet_serve_ttft_seconds",
                  "mxnet_device_bytes", "mxnet_serve_queue_wait_seconds"):
        assert known in fams


def test_suffix_shorthand_expands(tmp_path):
    mod = _load()
    md = tmp_path / "obs.md"
    md.write_text(
        "| `mxnet_cache_hits_total` / `_misses_total` | counter | |\n"
        "| `mxnet_a_bytes`, `mxnet_b_bytes` | gauge | |\n")
    doc = mod.documented_families(str(md))
    assert "mxnet_cache_hits_total" in doc
    assert "mxnet_cache_misses_total" in doc  # shorthand expanded
    assert "mxnet_a_bytes" in doc and "mxnet_b_bytes" in doc


def test_drift_is_detected(tmp_path):
    mod = _load()
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "from telemetry import counter, gauge\n"
        "counter('mxnet_documented_total').inc()\n"
        "gauge('mxnet_forgotten_bytes').set(1)\n"
        "counter(some_variable)  # non-literal: not checkable, skipped\n")
    md = tmp_path / "obs.md"
    md.write_text("| `mxnet_documented_total` | counter | | fine |\n")
    missing = mod.missing_families(root=str(pkg), md_path=str(md))
    assert missing == ["mxnet_forgotten_bytes"]
