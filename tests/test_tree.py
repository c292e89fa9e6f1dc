"""The tree against what is written about it: a document names no path
that is gone, an environment switch is a line in a list a reviewer
sees, and a record at the root has a reader.
"""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "veles_tpu")


with open(os.path.join(ROOT, ".gitignore")) as _fin:
    IGNORED = {line.strip().strip("/") for line in _fin}


def tree():
    """Every file of the checkout as a path from the root, less what
    building and running leave behind."""
    for folder, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d != ".git" and d not in IGNORED]
        for name in files:
            yield os.path.relpath(os.path.join(folder, name), ROOT)


FILES = sorted(tree())
BASENAMES = {os.path.basename(path) for path in FILES}
TOP = {path.split(os.sep)[0] for path in FILES if os.sep in path}
SUBPACKAGES = {path.split(os.sep)[1] for path in FILES
               if path.startswith("veles_tpu" + os.sep)
               and path.count(os.sep) > 1}
DOCUMENTS = ["README.md"] + [path for path in FILES
                             if path.startswith("docs" + os.sep)]


def exists(path):
    """``path``, or the module ``path.py``, once every trailing
    ``.attribute`` is off."""
    path = path.rstrip("/")
    while not (os.path.exists(path) or os.path.exists(path + ".py")):
        path, dot, attribute = path.rpartition(".")
        if not dot or "/" in attribute:
            return False
    return True


def missing(token):
    """Why ``token`` names a path that is not there, or None."""
    token = re.sub(r"::.*|:[0-9][0-9,-]*$", "", token)   # ::name, :line
    first, slash, _ = token.partition("/")
    if slash and first in TOP:
        return None if exists(os.path.join(ROOT, token)) else "no such path"
    if slash and first in SUBPACKAGES:
        return None if exists(os.path.join(PACKAGE, token)) \
            else "no such path under veles_tpu/"
    if re.fullmatch(r"\w+\.py", token):
        return None if token in BASENAMES else "no file of that name"
    if re.fullmatch(r"[A-Z0-9_]+\.(json|jsonl|md)", token):
        return None if os.path.exists(os.path.join(ROOT, token)) \
            else "not at the root"
    return None


@pytest.mark.parametrize("document", DOCUMENTS)
def test_paths_named_in_a_document_exist(document):
    with open(os.path.join(ROOT, document)) as fin:
        text = fin.read()
    gone = {}
    for quoted in re.findall(r"`([^`\n]+)`", text):
        for token in quoted.split():
            if not re.search(r"[*<$\{]", token):
                why = missing(token.strip("()[],;\"'"))
                if why:
                    gone[token] = why
    assert not gone, "%s names %s" % (document, gone)


#: Every ``VELES_*`` variable the package reads.  A new one is a line
#: here, which a reviewer sees (ROADMAP.md D8).
SWITCHES = {
    "VELES_AUTO_FUSE",
    "VELES_BACKEND",
    "VELES_CHAOS",
    "VELES_COORDINATOR",
    "VELES_DATA",
    "VELES_DEBUG_NONFINITE",
    "VELES_FLIGHT",
    "VELES_FLIGHT_CAPACITY",
    "VELES_FORGE_TOKEN",
    "VELES_NUM_PROCESSES",
    "VELES_PALLAS_BWD",
    "VELES_PIPELINE_INPUT",
    "VELES_PRECISION",
    "VELES_PRECISION_LEVEL",
    "VELES_PROCESS_ID",
    "VELES_PROFILE",
    "VELES_PROFILE_WINDOW",
    "VELES_QUANT_CALIB",
    "VELES_REQTRACE",
    "VELES_REQTRACE_EXEMPLARS",
    "VELES_REQTRACE_SAMPLE",
    "VELES_SCHEDULE_CACHE",
    "VELES_SEED",
    "VELES_SERIES_INTERVAL_S",
    "VELES_TEST_DATA",
    "VELES_TPU_SECRET",
}


def test_environment_switches_are_the_listed_ones():
    found = set()
    for path in FILES:
        if path.startswith("veles_tpu" + os.sep) and path.endswith(".py"):
            with open(os.path.join(ROOT, path)) as fin:
                found.update(re.findall(r"VELES_[A-Z0-9_]+", fin.read()))
    assert found == SWITCHES


def test_root_holds_no_unread_record():
    # COPYCHECK.json is the driver's: it writes it beside the ledger and
    # no file of the repository names it.
    records = {name for name in os.listdir(ROOT)
               if name.endswith(".json")} - {"BENCHMARK.json",
                                             "COPYCHECK.json"}
    for path in FILES:
        if path.split(os.sep)[0] in ("tests", "scripts", "veles_tpu",
                                     "docs"):
            with open(os.path.join(ROOT, path), errors="replace") as fin:
                text = fin.read()
            records -= {name for name in records if name in text}
    assert not records, "no file under tests/, scripts/, veles_tpu/ or " \
        "docs/ names %s" % sorted(records)
