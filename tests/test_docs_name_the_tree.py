"""The documents that describe the tree name files the tree has.

``README.md``, ``PARITY.md``, ``OVERLAP.md`` and the verify skill tell a
reader, who remembers no earlier session, what to open and what to run.
Every backticked path in them to a ``.py``, ``.md``, ``.json``, ``.yaml``,
``.csv`` or ``.sh`` file must exist in the checkout: at the root, or under
``acco_tpu/`` (the package's modules are written ``ops/losses.py``).

Not held: a pattern (``<`` or ``*``), a path into a run's output
(``outputs/``), an absolute path, the files a run writes into its own run
or checkpoint directory (``RUN_FILES``, named bare in the text), and a
citation of the reference implementation, which these documents write
``ref:<file>:<lines>``. ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are
histories: they name what was deleted, and are not held to this.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ("README.md", "PARITY.md", "OVERLAP.md", ".claude/skills/verify/SKILL.md")
PATH = re.compile(r"^[\w.-]+(/[\w.-]+)*\.(py|md|json|yaml|csv|sh)$")
# what main.py, the trainer and the checkpointer put into a run or checkpoint
# directory, and a user's own model file; the documents name them bare
RUN_FILES = {
    "config.yaml", "results.csv", "meta.json", "device_scopes.json", "model.json",
}


def named_paths(text: str) -> list[str]:
    found = []
    for span in re.findall(r"`([^`\n]+)`", text):
        for token in span.split():
            if "<" in token or "*" in token:
                continue
            token = token.split("::")[0].rstrip(".,;:)")
            token = re.sub(r":\d[\d,:-]*$", "", token)  # file.py:12-40
            if token.startswith("outputs/") or token in RUN_FILES:
                continue
            if PATH.match(token):
                found.append(token)
    return found


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists(doc):
    path = os.path.join(REPO, doc)
    if not os.path.exists(path):
        pytest.skip(f"{doc} is not in this checkout")
    with open(path, encoding="utf-8") as f:
        names = named_paths(f.read())
    assert names, f"{doc} names no file at all: the pattern has gone blind"
    missing = sorted({
        name for name in names
        if not os.path.exists(os.path.join(REPO, name))
        and not os.path.exists(os.path.join(REPO, "acco_tpu", name))
    })
    assert not missing, f"{doc} names files the tree does not have: {missing}"


def test_the_pattern_sees_what_it_should():
    text = (
        "run `python gone.py` then read `GONE.md`; see `ops/losses.py:42-63`, "
        "`tests/test_x.py::test_y`, `ref:trainer_base.py:77-97`, `outputs/a/b.json`, "
        "`<run_dir>/results.csv`, `results.csv`, `BENCH_*.json`, `/root/reference/main.py` "
        "and `train.remat=dots`."
    )
    assert named_paths(text) == [
        "gone.py", "GONE.md", "ops/losses.py", "tests/test_x.py",
    ]
