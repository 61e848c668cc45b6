"""The documents a new owner reads first name files that are there.

ISSUE 46: ``README.md`` advertised a deleted bench script and a "Measured" section
that said the repo had no benchmark, 24 PRs after it got one; root
``PERF.md`` was under its 300 lines at 132 KB.  One case a document: every
path it names in backticks exists in the checkout.  And no module of the
program or the benchmark imports a script."""

import ast
import fnmatch
import glob
import os
import re
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "PERF.md", "benchmarks/README.md"] + sorted(
    os.path.relpath(p, ROOT) for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
)
SUFFIXES = (".py", ".json", ".md", ".sh", ".cc")
# a path is looked for under each of these, in this order
BASES = ("", "xflow_tpu", "benchmarks", "scripts", "tests")
# THE allow-list: names with such a suffix that are no file of the checkout
NOT_IN_THE_CHECKOUT = {
    # the reference's own sources (liuhatry/xflow), cited by line
    "main.cc", "lr_worker.cc", "scripts/local.sh",
    # written by a run: the harness's last result, an artifact's and a
    # delta's manifest, a span trace an operator names
    ".last.json", "manifest.json", "delta_manifest.json", "trace.json",
}
# and whatever lies outside it (an absolute path) or under a directory that
# runs fill and ``.gitignore`` lists
OUTSIDE = ("/", ".bench_cache/", "chiprun_out/")
# a section whose heading carries this word records what WAS: it may name
# files that have gone (the heading's level bounds the section)
HISTORY = re.compile(r"\bhistory\b", re.IGNORECASE)


def _tracked() -> list[str]:
    out = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
        cwd=ROOT, capture_output=True, text=True,
    )
    if out.returncode == 0 and out.stdout:
        return [p for p in out.stdout.splitlines() if os.path.exists(os.path.join(ROOT, p))]
    # an unpacked archive: every file there is the checkout
    return [
        os.path.relpath(os.path.join(d, f), ROOT)
        for d, _, files in os.walk(ROOT) for f in files
    ]


@pytest.fixture(scope="module")
def checkout():
    files = _tracked()
    return set(files), {os.path.basename(p) for p in files}


def current_text(text: str) -> str:
    """``text`` without its history sections."""
    kept, skip_level = [], None
    for line in text.splitlines():
        heading = re.match(r"(#+)\s", line)
        if heading:
            level = len(heading.group(1))
            if skip_level is not None and level <= skip_level:
                skip_level = None
            if skip_level is None and HISTORY.search(line):
                skip_level = level
        if skip_level is None:
            kept.append(line)
    return "\n".join(kept)


def named_paths(text: str) -> set[str]:
    """Every word inside single backticks that ends in one of SUFFIXES,
    without a ``::name`` or ``:line`` tail."""
    found = set()
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = re.split(r"::|:\d", word.strip("()[],;\"'"))[0]
            if word.endswith(SUFFIXES):
                found.add(word)
    return found


def is_there(name: str, files: set, basenames: set) -> bool:
    if name in NOT_IN_THE_CHECKOUT or name.startswith(OUTSIDE):
        return True
    if "/" not in name and not set(name) & set("*<{"):
        return name in basenames  # "`step.py`", after its path was given
    # ``<cell>`` and ``{a,b}`` are read as a wildcard and as each of a, b
    pattern = re.sub(r"<[^>]*>", "*", name)
    alternatives = [pattern]
    brace = re.search(r"\{([^{}]*)\}", pattern)
    if brace:
        alternatives = [
            pattern[:brace.start()] + alt + pattern[brace.end():]
            for alt in brace.group(1).split(",")
        ]
    return all(
        any(
            fnmatch.filter(files, os.path.join(base, alt) if base else alt)
            for base in BASES
        ) or (
            "/" not in alt and bool(fnmatch.filter(basenames, alt))
        )
        for alt in alternatives
    )


def names_files_that_exist(document: str, checkout) -> None:
    files, basenames = checkout
    with open(os.path.join(ROOT, document)) as f:
        text = current_text(f.read())
    gone = sorted(
        name for name in named_paths(text) if not is_there(name, files, basenames)
    )
    assert not gone, f"{document} names files that are not in the checkout: {gone}"


def is_held_to_its_size(document: str, checkout) -> None:
    """PERF.md, which every session reads whole: at most 300 lines, none
    over 1 000 characters, under 60 KB (a count of lines alone was kept by
    lengthening them: 262 lines and 132 KB at PR 45)."""
    with open(os.path.join(ROOT, document), "rb") as f:
        raw = f.read()
    lines = raw.decode().splitlines()
    assert len(lines) <= 300
    assert max(len(line) for line in lines) <= 1000
    assert len(raw) < 60 * 1024


@pytest.mark.parametrize(
    "document, rule",
    [(d, names_files_that_exist) for d in DOCUMENTS]
    + [("PERF.md", is_held_to_its_size)],
    ids=lambda v: v if isinstance(v, str) else v.__name__,
)
def test_document(document, rule, checkout):
    rule(document, checkout)


def test_the_reader_of_documents_reads_what_it_should():
    assert len(DOCUMENTS) >= 12  # the glob found docs/
    text = "# A\n`a.py` and `python scripts/b.py --x` `c.py::f` `d/e.json:12`\n" \
           "## Round 3 (history)\n`gone.py`\n### deeper\n`gone2.py`\n## Now\n`f.md`\n"
    assert named_paths(current_text(text)) == {
        "a.py", "scripts/b.py", "c.py", "d/e.json", "f.md"
    }
    files = {"xflow_tpu/parallel/step.py", "benchmarks/configs/lr.json", "docs/x_a.json", "docs/x_b.json"}
    names = {os.path.basename(p) for p in files}
    assert is_there("step.py", files, names)
    assert is_there("parallel/step.py", files, names)
    assert is_there("configs/<name>.json", files, names)
    assert is_there("docs/x_{a,b}.json", files, names)
    assert not is_there("docs/x_{a,c}.json", files, names)
    assert not is_there("gone.py", files, names)
    assert not is_there("scripts/step.py", files, names)
    assert is_there("/root/TESTS_LAST_RUN.json", files, names)


# -- no module of the program or the benchmark imports a script ---------------

# the one script that is a library too: the synthetic-corpus generator
# (``chip_smoke.py`` and ``benchmarks/generators`` import it by this name)
SCRIPT_LIBRARIES = {"scripts.gen_synth"}


def _imports(path: str) -> set[str]:
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found |= {alias.name for alias in node.names}
        elif isinstance(node, ast.ImportFrom) and node.level == 0 and node.module:
            found.add(node.module)
            found |= {f"{node.module}.{alias.name}" for alias in node.names}
    return found


def test_no_module_imports_a_script(checkout):
    files, _ = checkout
    root_scripts = {
        p[:-3] for p in files if p.endswith(".py") and "/" not in p
    }
    offenders = []
    for path in sorted(files):
        if not path.endswith(".py") or not path.startswith(("xflow_tpu/", "benchmarks/")):
            continue
        for name in _imports(os.path.join(ROOT, path)):
            top = name.split(".")[0]
            if (top == "scripts" and name != "scripts" and not any(
                name == lib or name.startswith(lib + ".") for lib in SCRIPT_LIBRARIES
            )) or top in root_scripts:
                offenders.append((path, name))
    assert not offenders, offenders
