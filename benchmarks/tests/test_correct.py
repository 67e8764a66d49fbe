"""``correct`` has to be able to fail: the control and the planted faults.

Each case drives a whole run of the command at the rehearsal size on the
CPU (the harness's look for a chip is skipped with ``--rehearse``), and
reads the result line.  The control is the reference in fp8 put in the
program's place; the faults are planted underneath the timed path.
"""

import io
import json
from contextlib import redirect_stdout

import pytest

from benchmarks import run


def _run(config, traffic, seed, sabotage=None, control=None, seconds="3"):
    argv = ["--rehearse", "--config", config, "--traffic", traffic, "--seed", str(seed), "--seconds", seconds, "--trace", "0"]
    if control:
        argv += ["--control", control]
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main(argv, sabotage=sabotage) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("config,traffic,number", [
    ("rehearsal-tiny", "rehearsal-open", "score_err"),
    ("rehearsal-tiny-rag", "rehearsal-closed", "rerank_err"),
])
def test_sound_run_is_correct_and_control_is_not(config, traffic, number):
    line = _run(config, traffic, 2_500_000_011, control="fp8")
    assert line["correct"] is True, line["compared"]
    assert list(line)[-1] == "compared"
    assert line["control"]["correct"] is False
    limit = line["compared"][number]["limit"]
    assert line["control"]["numbers"][number] > limit > line["compared"][number]["value"]


class _Altered:
    """A completion handle whose answers come back with the first document
    of every row replaced by another one."""

    def __init__(self, handle, n_keys):
        self._handle, self._n_keys = handle, n_keys

    def __getattr__(self, name):
        return getattr(self._handle, name)

    def __call__(self):
        res = self._handle()
        for row in res:
            if row:
                row[0] = ((row[0][0] + 1000) % self._n_keys, row[0][1])
        return res


def _alter_answers(system):
    """A document key altered where the answer is produced (the target the
    scheduler drives: stage 1 alone, or the rerank pipeline)."""
    inner = system.target.submit

    def submit(texts, k=None, **kw):
        return _Altered(inner(texts, k, **kw), system.space.n_keys)

    system.target.submit = submit


def _ingest_does_nothing(system):
    """A step that returns its state unchanged: the index ignores what the
    ingest runner commits, which still reports the documents as absorbed."""
    system.index.add = lambda keys, vectors: None


def _drop_a_shard(system):
    """The exchange between chips left out: shard 3's candidates never reach
    the merge (every row of it is masked)."""
    import jax.numpy as jnp

    child = system.index.shards[3]
    child._bias = jnp.full_like(child._bias, -jnp.inf)


@pytest.mark.parametrize("config,traffic,fault", [
    ("rehearsal-tiny", "rehearsal-open", _alter_answers),
    ("rehearsal-tiny-rag", "rehearsal-closed", _alter_answers),
    ("rehearsal-tiny", "rehearsal-commits", _ingest_does_nothing),
    ("rehearsal-tiny-x4", "rehearsal-open", _drop_a_shard),
], ids=["altered-answer", "altered-answer-rerank", "ingest-unchanged", "shard-left-out"])
def test_planted_fault_is_not_correct(config, traffic, fault):
    line = _run(config, traffic, 2_500_000_013, sabotage=fault)
    assert line["correct"] is False, line["compared"]
    failed = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    assert failed, line["compared"]
