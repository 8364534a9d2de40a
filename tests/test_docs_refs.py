"""The documents a reader starts from name files that are there.

`bench.py` lived on in five documents after it stopped being the benchmark;
this is the check that would have said so.  The plan files (ROADMAP.md,
PERF.md, CHANGES.md, ISSUE.md) name deleted files on purpose and are not
scanned."""
import glob
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SKIP_DIRS = {".git", "build", "chiprun_out", ".jax_cache", "__pycache__",
              ".pytest_cache"}
# Files the program writes at run time (a checkpoint directory's manifest).
_WRITTEN_AT_RUN_TIME = {"MANIFEST.json"}
# `path/to/file.py`, optionally followed by `::test`, `:12` or ` run_x`
# inside the same backticks.
_TOKEN = re.compile(r"`([A-Za-z0-9_./-]+\.(?:py|json|md|cpp))(?=[`: ])")


def _checkout_files():
    out = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        rel = os.path.relpath(root, REPO)
        out += [os.path.normpath(os.path.join(rel, f)) for f in files]
    return out


def test_documents_name_files_that_exist():
    """A backticked path resolves when some file of the checkout is it or
    ends with it (`ops/attention.py` for `ray_tpu/ops/attention.py`)."""
    files = _checkout_files()
    docs = [os.path.join(REPO, "README.md"),
            *sorted(glob.glob(os.path.join(REPO, "docs", "*.md")))]
    assert len(docs) > 5
    missing = []
    for doc in docs:
        with open(doc) as f:
            text = f.read()
        for name in sorted(set(_TOKEN.findall(text))):
            want = os.path.normpath(name)
            if os.path.basename(want) in _WRITTEN_AT_RUN_TIME:
                continue
            if not any(p == want or p.endswith(os.sep + want)
                       for p in files):
                missing.append(f"{os.path.relpath(doc, REPO)}: {name}")
    assert not missing, "documents name files that do not exist:\n" + \
        "\n".join(missing)
