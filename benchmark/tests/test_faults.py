"""The rest of a run with the timed path broken underneath: ``correct``
has to come out false. Skips the harness's look for a chip (a CPU server
at a narrowed shard width and a cut scale, tests/planted.py), drives
everything else: boot, load, warm-up, window, comparison.

Faults a served cell can have: an answer altered where the client
receives it, and answers produced from other data than was loaded (bits
cleared under the server after the load). The control is the reference
itself with a guarantee broken (one part of the data lost), put in the
program's place.

Run by hand (minutes): ``python -m pytest benchmark/tests/test_faults.py -q``
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("PILOSA_TPU_SHARD_WIDTH_EXP", "16")

import json  # noqa: E402

import pytest  # noqa: E402

from benchmark import run as bench_run  # noqa: E402
from benchmark.tests import planted  # noqa: E402

with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def _run(workload):
    with planted.on_cpu(2):
        return bench_run.run(workload, 11, 2.0, False)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_the_control_is_not(workload):
    verdict = {}
    with planted.control(verdict):
        line = _run(workload)
    assert line["correct"] is True
    assert line["compared"]["mismatched"]["value"] == 0
    assert line["compared"]["answers_compared"]["value"] > 50
    assert verdict["mismatched"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_an_altered_answer_is_not_correct(workload):
    with planted.altered_replies(3):
        line = _run(workload)
    assert line["correct"] is False
    assert line["compared"]["mismatched"]["value"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_answers_from_other_data_than_was_loaded_are_not_correct(workload):
    with planted.cleared_after_load():
        line = _run(workload)
    assert line["correct"] is False
    assert line["compared"]["mismatched"]["value"] > 0
