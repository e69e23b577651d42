"""The documents name files that exist, and the registry names knobs that
are read.

One case a document: `README.md` and every `docs/*.md` but the historical
`docs/PERF_NOTES.md`. `PERF.md` and `ROADMAP.md` are not cases: they name
files of the past and of the future by design. Stdlib only, no jax.
"""
import ast
import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: what building, testing and running leave behind (.gitignore), and git
_SKIP_DIRS = {".git", "__pycache__", ".pytest_cache", ".jax_cache",
              "chiprun_out", ".perfbench_out", ".parent_tree", "build",
              "dist"}

_SUFFIXES = (".py", ".json", ".sh", ".c", ".md")
_PREFIXES = ("tools/", "docs/", "ci/", "example/", "incubator_mxnet_tpu/")

#: paths a document describes as deleted, or as files of the reference
#: (`/root/reference`), of a user's own tree or of an output directory
ALLOWED = {
    "README.md": {
        "train.py",                     # the user's script behind launch.py
        "env_var.md",                   # the reference's document
    },
    "docs/CONVERGENCE.md": {            # the reference's examples
        "example/gluon/mnist", "example/gluon/dcgan.py", "example/ssd",
        "example/recommenders",
    },
    "docs/LOADGEN.md": {"report.json"},             # a run's output
    "docs/OBSERVABILITY.md": {"a.json", "b.json", "train.py"},
    "docs/PERF_INT8.md": {              # the reference's files
        "example/quantization/imagenet_inference.py",
    },
    "docs/PERF_XING4.md": {"diag.py"},  # a builder's script in .perfbench_out/
    "docs/PERF_RESNET.md": {            # deleted in PR 29, and said so
        "ops/conv_bwd.py", "tests/test_conv_bwd.py",
        "benchmark/conv_bwd_pilot.py",
    },
    "docs/STATIC_ANALYSIS.md": {"lint_reports.json", "some/file.py"},
}


@functools.lru_cache(maxsize=None)
def _tree():
    """-> (the tree's files, every tail of a file's or directory's path at
    a `/`), walked once a process and on the first test's demand."""
    files, dirs = set(), set()
    for base, subdirs, names in os.walk(ROOT):
        subdirs[:] = [d for d in subdirs if d not in _SKIP_DIRS
                      and not d.startswith(".smoke_tree")]
        rel = os.path.relpath(base, ROOT).replace(os.sep, "/")
        rel = "" if rel == "." else rel + "/"
        dirs.update(rel + d for d in subdirs)
        files.update(rel + n for n in names)
    tails = set()
    for path in files | dirs:
        parts = path.split("/")
        tails.update("/".join(parts[i:]) for i in range(len(parts)))
    return files, tails


def named_paths(text):
    """The repository paths `text` names in backticks: of every word inside
    a pair of backticks (or a fenced block), those that end like a source
    or record file or start like one of the tree's directories, with a
    `:line` or `::test` suffix stripped. Patterns and placeholders (`*`,
    `<...>`, `{a,b}`, `$VAR`) name no one file and are left out."""
    found = set()
    for span in re.findall(r"`+([^`]+)`+", text):
        for word in span.split():
            word = word.strip("()[],;'\"")
            word = re.sub(r"(::[\w\[\]-]+)+$", "", word)
            word = re.sub(r":\d+(-\d+)?(,:?\d+(-\d+)?)*$", "", word)
            word = word.rstrip(".:")
            if re.search(r"[*<>{}$=|…%\\]|\.\.\.", word) or "://" in word:
                continue
            if word.startswith(("/", "~", "-")):
                continue            # another machine's path, or an option
            if word.endswith(_SUFFIXES) or (word.startswith(_PREFIXES)
                                            and len(word.split("/")) > 1):
                found.add(word)
    return found


def exists(path):
    """A path names a file or directory of the tree when it is one from the
    root, or the tail of one at a `/` (`ops/lm_ce.py`, `run.sh`)."""
    return path.rstrip("/") in _tree()[1]


DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, ROOT).replace(os.sep, "/")
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
    if os.path.basename(p) != "PERF_NOTES.md")


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(ROOT, document), encoding="utf-8") as f:
        text = f.read()
    allowed = ALLOWED.get(document, set())
    missing = sorted(p for p in named_paths(text)
                     if p not in allowed and not exists(p))
    assert not missing, (
        "%s names paths the tree does not hold: %s" % (document, missing))


def test_the_parser_sees_what_it_should():
    text = ("see `tools/loadgen.py:63-64`, `tests/test_x.py::test_a[b]` and "
            "`python gone.py --gate`; not `perfbench/workloads/<cell>.json`, "
            "`docs/PERF_*.md` or `--json`.\n```\npython ci/none.sh\n```\n")
    assert named_paths(text) == {"tools/loadgen.py", "tests/test_x.py",
                                 "gone.py", "ci/none.sh"}
    assert exists("tools/loadgen.py") and exists("ops/lm_ce.py")
    assert exists("run.sh") and exists("incubator_mxnet_tpu/ops/")
    assert not exists("gone.py") and not exists("ci/none.sh")


def test_every_registered_knob_is_read_somewhere():
    """Each name in `config.ENV_VARS` appears in the tree's code outside the
    registry's own literal (the package, `tools/`, the language bindings'
    shims, `ci/`, the tests' own switches): a knob nothing reads is a
    documented option that does nothing. Documents do not count."""
    cfg = os.path.join(ROOT, "incubator_mxnet_tpu", "config.py")
    with open(cfg, encoding="utf-8") as f:
        source = f.read()
    registry = next(
        node for node in ast.parse(source).body
        if isinstance(node, ast.Assign)
        and any(getattr(t, "id", None) == "ENV_VARS" for t in node.targets))
    names = [k.value for k in registry.value.keys]
    assert len(names) > 100
    lines = source.splitlines(True)
    corpus = [
        "".join(lines[:registry.lineno - 1] + lines[registry.end_lineno:])]
    code = (".py", ".sh", ".c", ".cc", ".h", ".hpp", ".jl", ".R", ".pm",
            ".xs", ".scala")
    for path in sorted(_tree()[0]):
        if path.endswith(code) and path != "incubator_mxnet_tpu/config.py" \
                and path != "tests/test_docs_paths.py":
            with open(os.path.join(ROOT, path), encoding="utf-8",
                      errors="replace") as f:
                corpus.append(f.read())
    corpus = "\n".join(corpus)
    unread = [n for n in names
              if not re.search(r"\b%s\b" % re.escape(n), corpus)]
    assert not unread, "registered and read nowhere: %s" % unread
