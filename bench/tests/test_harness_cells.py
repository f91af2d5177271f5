"""The benchmark's cells resolve by name, a tiny run of each traffic driver
ends in the contract's result line, and the chip guard refuses the CPU."""
from __future__ import annotations

import json

import pytest

from tiny_cells import CONTRACT_KEYS, REPO, fake_chips, make_root

import run as bench  # noqa: E402


def _cells():
    return [w["name"] for w in
            json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", _cells())
def test_cell_resolves_by_name(name):
    found = bench.resolve(REPO, name)
    e2e = {m["name"] for m in found["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert found["per_layer"], "every cell reports a per-layer metric"
    for m in found["per_layer"]:
        assert m["moves"] in e2e
        assert found["readers"][m["name"]].exists()
    assert found["driver"].exists()
    assert found["config"]["name"] == found["cell"]["config"]
    assert int(found["config"]["check_pairs"]) > 0


@pytest.mark.parametrize("kind", ["closed", "open"])
def test_tiny_run_prints_contract_line(kind, tmp_path, capsys):
    root = make_root(tmp_path)
    rc = bench.main(["--workload", f"tiny.{kind}", "--seed", "4294967311",
                     "--seconds", "1", "--trace", "0"],
                    root=root, devices=fake_chips, compile_cache=False)
    assert rc == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    line = json.loads(last)
    assert set(line) <= CONTRACT_KEYS
    assert list(line)[-1] == "check"
    assert line["correct"] is True and line["failed"] == 0
    want = {"qps", "setup_s"} if kind == "closed" else \
        {"p50_ms", "setup_s"}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "tpu"


def test_guard_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        bench.require_chip(1)
    assert e.value.code not in (0, None)
    with pytest.raises(SystemExit) as e:
        bench.main(["--workload", _cells()[0], "--seed", "1",
                    "--seconds", "1"], compile_cache=False)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
