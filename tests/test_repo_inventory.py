"""What the repository says it has, held to what it has (stdlib only).

The switch table of README.md and the package's environment reads name
the same `DPARK_*` variables; conf.py's count of reads only shrinks; and
every repo path a document names, or runs, exists in the tree."""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# conf.py's environment reads after the AOT executable cache and the
# program-cost capture left.  A PR that adds one says here, against the
# rule at the head of conf.py, who sets it.
CONF_ENV_READS_MAX = 65

DOCUMENTS = ["README.md", "PERF.md", ".claude/skills/verify/SKILL.md",
             ".github/workflows/ci.yml"]


def _read(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
        return f.read()


def _package_switches():
    """Every DPARK_* name the package holds as a string: the key of an
    environment read, or of a getattr on conf."""
    names = set()
    for dirpath, _, files in os.walk(os.path.join(ROOT, "dpark_tpu")):
        for name in files:
            if name.endswith(".py"):
                text = _read(os.path.join(dirpath, name))
                names.update(re.findall(r"[\"'](DPARK_[A-Z0-9_]+)[\"']", text))
    return names


def _table_switches():
    section = _read("README.md").split("\n## Switches\n", 1)[1]
    section = section.split("\n## ", 1)[0]
    return set(re.findall(r"^\| `(DPARK_[A-Z0-9_]+)` \|", section, re.M))


def test_every_switch_the_package_reads_is_in_the_readme_table():
    assert sorted(_package_switches() - _table_switches()) == []


def test_every_switch_in_the_readme_table_is_read_by_the_package():
    assert sorted(_table_switches() - _package_switches()) == []


def test_conf_holds_no_more_environment_reads_than_it_did():
    reads = len(re.findall(r"os\.environ\.get\(", _read("dpark_tpu/conf.py")))
    assert reads <= CONF_ENV_READS_MAX, (
        "conf.py reads %d environment variables, %d allowed: a new read "
        "needs someone who sets it (the rule at the head of conf.py)"
        % (reads, CONF_ENV_READS_MAX))


def _expand_braces(path):
    m = re.search(r"\{([^{}]*)\}", path)
    if m is None:
        return [path]
    return [p for alt in m.group(1).split(",")
            for p in _expand_braces(path[:m.start()] + alt + path[m.end():])]


def _named_paths(text):
    """Paths rooted at a top-level entry of the repo (`tests/x.py`,
    `perf/configs/*.json`, `tools/{dlint,drun}`), and the file of every
    `python <file>.py` / `pytest <file>.py`.  A path with a placeholder
    (`<cell>`, `$DIR`) ends where the placeholder starts."""
    tops = sorted(e for e in os.listdir(ROOT)
                  if os.path.isdir(os.path.join(ROOT, e)))
    rooted = re.compile(r"(?<![\w/.$<>~-])((?:%s)/[\w.{},*/-]*)"
                        % "|".join(re.escape(t) for t in tops))
    found = set()
    for m in rooted.finditer(text):
        path = m.group(1).rstrip(".,-")
        if path.count("{") == path.count("}"):
            found.update(_expand_braces(path))
    for m in re.finditer(r"\b(?:python3?|pytest)\s+([^\s`'\"]+\.py)\b", text):
        if not re.search(r"[<>$]", m.group(1)) \
                and not os.path.isabs(m.group(1)):
            found.add(m.group(1))
    return sorted(found)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    missing = [p for p in _named_paths(_read(document))
               if not glob.glob(os.path.join(ROOT, p))]
    assert not missing, "%s names what is not in the tree: %s" % (
        document, missing)
