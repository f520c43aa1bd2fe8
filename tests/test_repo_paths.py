"""The documents name files that exist.

Every repo-relative file or directory that a user-facing document names —
in backticks, in a fenced block, or as an argument of a make recipe — is
there, and no docstring or comment under ``splink_tpu/`` sends the reader
to a root-level file, or to one of a ``scripts`` or ``benchmarks``
directory, that is not. A deleted script lives on in prose long after its
last import has gone (PR 31 removed a benchmark generation that fifty-eight
lines of these documents still offered). History files — ``CHANGES.md``, ``PERF.md``,
``ROADMAP.md``, ``ADVICE.md``, ``PAPER*.md`` — say what WAS and are exempt.
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = (
    ["README.md"]
    + sorted(os.path.relpath(p, ROOT)
             for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))
    + ["Makefile", os.path.join(".claude", "skills", "verify", "SKILL.md")]
)
SUFFIXES = (".py", ".md", ".json")


def _ignored() -> list[str]:
    """Directory names ``.gitignore`` lists: what building and running
    leave behind (``.jax_cache/``, ``_chipcheck/``) is not in a checkout."""
    with open(os.path.join(ROOT, ".gitignore")) as f:
        return [ln.strip() for ln in f if ln.strip().endswith("/")]


def _repo_files() -> set[str]:
    """Base names of every file of the checkout (a bare ``perf_smoke.py``
    or ``perf_baselines.json`` names one of them)."""
    names: set[str] = set()
    skip = {".git", "__pycache__", "chiprun_out"} | {
        d.rstrip("/") for d in _ignored()
    }
    for _dir, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in skip]
        names.update(files)
    return names


def _program_source() -> str:
    """The Python text of the package and its smokes: a bare
    ``aot_menu.json`` or ``em_checkpoint.json`` is a file the PROGRAM writes
    where its user points it, and is real if the program holds the name."""
    parts = []
    for pattern in (("splink_tpu", "**", "*.py"), ("scripts", "*.py")):
        for path in glob.glob(os.path.join(ROOT, *pattern), recursive=True):
            with open(path) as f:
                parts.append(f.read())
    return "\n".join(parts)


def named_paths(text: str, recipes: bool = False) -> list[str]:
    """The path-like tokens of a document's code spans."""
    fenced = re.findall(r"```[^\n]*\n(.*?)```", text, re.S)
    text = re.sub(r"```[^\n]*\n.*?```", "", text, flags=re.S)
    spans = [ln for block in fenced for ln in block.splitlines()]
    spans += re.findall(r"`([^`\n]+)`", text)
    if recipes:
        spans += [ln for ln in text.splitlines() if ln.startswith("\t")]
    found = []
    for span in spans:
        for tok in span.split():
            tok = tok.strip("()[],;'\"").split("::")[0]
            tok = re.sub(r":[\d,\-]+$", "", tok).rstrip(".:")
            if not tok or re.search(r"[<>*{}$=|@#(\"]", tok):
                continue  # a placeholder, a glob, an assignment, a call
            if tok.startswith(("/", "~", "-", "http")):
                continue  # not relative to the repo
            if tok.endswith(SUFFIXES) or (tok.endswith("/") and tok != "/"):
                found.append(tok)
    return found


@pytest.fixture(scope="module")
def known():
    """(files of the checkout, ignored directories, program text), read once."""
    return _repo_files(), _ignored(), _program_source()


def _exists(tok: str, repo_files: set[str], ignored: list[str],
            program_source: str) -> bool:
    if any(f"/{d}" in f"/{tok}" for d in ignored):
        return True
    if "/" not in tok:
        return tok in repo_files or f'"{tok}"' in program_source
    return any(
        os.path.exists(os.path.join(ROOT, base, tok))
        for base in ("", "splink_tpu")  # docs name modules package-relative
    )


@pytest.mark.parametrize("document", DOCUMENTS)
def test_named_paths_exist(document, known):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    tokens = named_paths(text, recipes=document == "Makefile")
    assert tokens, f"{document}: the scan found no path at all"
    missing = sorted({t for t in tokens if not _exists(t, *known)})
    assert not missing, f"{document} names what is not there: {missing}"


def test_package_cites_no_missing_file(known):
    """No docstring or comment under ``splink_tpu/`` names a root-level
    file, or a path under ``scripts`` or ``benchmarks``, that does not
    exist."""
    root_level = re.compile(
        r"(?<![\w/.\-])((?:scripts|benchmarks)/[\w./\-]*\w|[A-Za-z_]\w*\.(?:py|md))(?![\w/])"
    )
    repo_files = known[0]
    missing = []
    for path in glob.glob(os.path.join(ROOT, "splink_tpu", "**", "*"),
                          recursive=True):
        if not path.endswith((".py", ".json", ".cpp")):
            continue
        with open(path, errors="replace") as f:
            for n, line in enumerate(f, 1):
                for tok in root_level.findall(line):
                    # a bare name is some file of the checkout: a module
                    # named beside its package, a root-level script
                    if not (os.path.exists(os.path.join(ROOT, tok))
                            if "/" in tok else tok in repo_files):
                        missing.append(
                            f"{os.path.relpath(path, ROOT)}:{n}: {tok}")
    assert not missing, "\n".join(missing)
