"""The rest of a run with the timed path broken underneath: a worker
entry that alters served tokens where they are produced drives the
same harness (its look for a chip skipped) and `correct` comes out
false; so does a served count that is short."""

import json
import os
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import toyroot  # noqa: E402
from test_bench_harness_run import REPO, run_cell  # noqa: E402

BROKEN_WORKER = textwrap.dedent('''
    """The benchmark's worker entry with one fault put under it: every
    fourth decode tick hands back a wrong token in every row."""
    import importlib.util
    import os
    import sys

    spec = importlib.util.spec_from_file_location(
        "perfbench_worker", os.path.join({repo!r}, "perfbench", "worker",
                                         "serve_worker.py"))
    worker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(worker)

    from dcos_commons_tpu.serve import pool

    decode = pool.PagedPoolModel.decode
    ticks = [0]

    def broken(self, *args, **kwargs):
        out = decode(self, *args, **kwargs)
        ticks[0] += 1
        if ticks[0] % 4 == 0:
            out = (out + 1) % self.config.vocab
        return out

    pool.PagedPoolModel.decode = broken
    raise SystemExit(worker.main())
''')


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return toyroot.build(str(tmp_path_factory.mktemp("bench")))


def test_altered_tokens_come_out_as_not_correct(toy, tmp_path):
    with open(tmp_path / "serve_worker.py", "w") as f:
        f.write(BROKEN_WORKER.format(repo=REPO))
    proc = run_cell(toy, "--rehearse-cpu", "--worker-dir", str(tmp_path),
                    trace="0")
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert result["correct"] is False
    assert result["failed"] == 0  # every count and range was in order
    assert set(result["metrics"]) == {"norm_lat_p50_s", "setup_s"}


def test_a_short_or_out_of_range_answer_counts_as_failed():
    sys.path.insert(0, REPO)
    from perfbench import run
    from perfbench.harness.loadgen import Outcome
    from perfbench.harness.traffic import Request

    def outcome(status, tokens, asked=4):
        return Outcome(Request(0, "window", 0.0, 3, asked, None),
                       status=status, tokens=tokens)

    judged = [
        outcome(200, [1, 2, 3, 4]), outcome(200, [1, 2, 3]),
        outcome(200, [1, 2, 3, 128]), outcome(503, None), outcome(0, None),
    ]
    failed, reasons = run.check_answers(judged, 128)
    assert failed == 4 and len(reasons) == 4
