"""``correct`` comes out false when the served path is broken underneath a
run, once for each fault a cell of this service can have, and for the
control; the sound path reads correct. Tiny cells on the CPU, with the
chip guard faked."""
from __future__ import annotations

import time

import pytest

from tiny_cells import TINY, fake_chips, make_root

import control  # noqa: E402
import run as bench  # noqa: E402
from harness import faults  # noqa: E402


@pytest.fixture(scope="module")
def envs(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("tiny"))
    return {kind: bench.prepare(f"tiny.{kind}", root=root,
                                devices=fake_chips, compile_cache=False)
            for kind in ("closed", "open")}


def _run(env):
    return bench.execute(env, 2_147_483_659, 1.0, False,
                         time.perf_counter())[0]


@pytest.mark.parametrize("kind", ["closed", "open"])
@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_reads_incorrect(envs, kind, fault):
    sess = envs[kind]["session"]
    if fault == "misrouted":
        faults.misrouted(sess, TINY["open"]["pairs_per_request"])
    else:
        faults.FAULTS[fault](sess)
    try:
        res = _run(envs[kind])
    finally:
        faults.restore(sess)
    assert res["correct"] is False
    assert res["check"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("kind", ["closed", "open"])
def test_sound_path_reads_correct(envs, kind):
    res = _run(envs[kind])
    assert res["correct"] is True
    assert res["check"]["wrong_answers"]["value"] == 0


@pytest.mark.parametrize("kind", ["closed", "open"])
@pytest.mark.parametrize("seed", [3, 4_000_000_007, 12])
def test_control_reads_incorrect(envs, kind, seed):
    res = control.control_run(envs[kind], seed, 1.0)
    assert res["correct"] is False
    assert res["check"]["wrong_answers"]["value"] > 0
    assert res["check"]["unanswered"]["value"] == 0
    assert _run(envs[kind])["correct"] is True     # the session is restored
