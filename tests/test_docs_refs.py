"""What the documents and the Makefile name exists.

A reader of ``README.md``, ``docs/*.md`` or the verify skill who runs
what the page says must find the file and the ``make`` target; a recipe
must find its script.
"""
import glob
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md", ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)

#: a repository file by its name: a path with one of the tree's suffixes,
#: maybe a ``:line`` or ``:line-line`` after it
_PATH = re.compile(
    r"(?<![\w/.-])([\w.-]+(?:/[\w.-]+)*\.(?:py|md|json|jsonl|cpp|c|sh|txt))"
    r"(?::\d+(?:-\d+)?)?(?![\w/-])"
)
#: where a bare name may live: the package and its parts, tests, examples
_ROOTS = ["", "dragonboat_tpu", "dragonboat_tpu/ops", "dragonboat_tpu/raft",
          "dragonboat_tpu/obs", "dragonboat_tpu/logdb",
          "dragonboat_tpu/transport", "tests", "examples"]
#: files of the reference project (lni/dragonboat) the pages cite by name
_REFERENCE = {"docs/test.md"}


def _code(text):
    """The text inside backticks, and each command line (continuations
    joined) of the fenced blocks."""
    fenced = re.findall(r"```.*?\n(.*?)```", text, flags=re.S)
    lines = [
        ln.strip()
        for block in fenced
        for ln in block.replace("\\\n", " ").splitlines()
    ]
    text = re.sub(r"```.*?```", "", text, flags=re.S)
    return lines + re.findall(r"`([^`\n]+)`", text)


def _make_targets():
    with open(os.path.join(REPO, "Makefile")) as f:
        return set(re.findall(r"^([a-z][\w-]*):", f.read(), flags=re.M))


def _exists(name):
    return any(os.path.exists(os.path.join(REPO, r, name)) for r in _ROOTS)


def test_docs_name_only_paths_and_make_targets_that_exist():
    targets = _make_targets()
    missing = []
    for doc in DOCS:
        with open(os.path.join(REPO, doc)) as f:
            spans = _code(f.read())
        for span in spans:
            names = _PATH.findall(span)
            if re.match(r"(\S+=\S+ )*python3? ", span):
                names = names[:1]  # the script, not the files it is given
            for name in names:
                # a path under a placeholder (`<repo>/...`, `/tmp/...`)
                # is not the repository's
                if name in _REFERENCE or re.search(
                    r"[<>*$]|/tmp/|/var/", span
                ):
                    continue
                # a bare lower-case data file is something a command
                # writes (`merged.json`), not a file of the tree
                if re.fullmatch(r"[a-z][\w.-]*\.(json|jsonl|txt)", name):
                    continue
                if not _exists(name):
                    missing.append((doc, name))
            for target in re.findall(r"\bmake ([a-z][\w-]*)", span):
                if target not in targets:
                    missing.append((doc, "make " + target))
    assert not missing, missing


def test_every_makefile_recipe_finds_its_script():
    with open(os.path.join(REPO, "Makefile")) as f:
        recipes = [ln for ln in f.read().splitlines() if ln.startswith("\t")]
    assert recipes
    missing = []
    for ln in recipes:
        for name in _PATH.findall(ln):
            if not os.path.exists(os.path.join(REPO, name)):
                missing.append(name)
        for d in re.findall(r"-C (\S+)", ln):
            if not os.path.isdir(os.path.join(REPO, d)):
                missing.append(d)
    assert not missing, missing
