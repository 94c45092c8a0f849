"""A fault of the new cell's own kind: a server whose ``>`` answers as
``>=`` (tests/gt_as_ge/sitecustomize.py, on the server child's
PYTHONPATH for the length of the run). Three of the mix's five templates
ask ``>``, and the thresholds are amounts that rides have, so ``correct``
has to come out false by ``mismatched``; the templates that ask ``>=``,
``<`` and a band still agree. Skips the harness's look for a chip, as
test_faults.py does.

Run by hand: ``python -m pytest benchmark/tests/test_range_faults.py -q``
"""

import contextlib
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("PILOSA_TPU_SHARD_WIDTH_EXP", "16")

from benchmark import run as bench_run  # noqa: E402
from benchmark.tests import planted  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "taxi-128r.adhoc_range"


@contextlib.contextmanager
def greater_answers_as_greater_or_equal():
    before = os.environ.get("PYTHONPATH")
    os.environ["PYTHONPATH"] = os.pathsep.join(p for p in (os.path.join(HERE, "gt_as_ge"), before) if p)
    try:
        yield
    finally:
        if before is None:
            del os.environ["PYTHONPATH"]
        else:
            os.environ["PYTHONPATH"] = before


def test_a_server_whose_greater_answers_as_greater_or_equal_is_not_correct(capfd):
    with planted.on_cpu(2), greater_answers_as_greater_or_equal():
        line = bench_run.run(CELL, 13, 2.0, False)
    assert line["correct"] is False
    assert line["compared"]["mismatched"]["value"] > 0 and line["compared"]["unanswered"]["value"] == 0
    # only the templates that ask ">" are at fault
    wrong = [ln for ln in capfd.readouterr().err.splitlines() if ln.startswith("not equal: ")]
    assert wrong and all(" > " in ln for ln in wrong)


def test_the_same_run_without_the_fault_is_correct():
    with planted.on_cpu(2):
        line = bench_run.run(CELL, 13, 2.0, False)
    assert line["correct"] is True and line["compared"]["answers_compared"]["value"] > 50
