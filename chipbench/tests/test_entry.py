"""The measurement entry fails, and prints no result, where it cannot
measure."""

import os
import subprocess
import sys

import pytest

from chipbench import run


def test_entry_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(run.HERE / "run.py"), "--workload",
         "heap_pb.pairs", "--seed", str(2**31 + 11), "--seconds", "1",
         "--trace", "0"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_program_is_taken_from_beside_the_benchmark_only(tmp_path,
                                                          monkeypatch):
    monkeypatch.setattr(run, "ROOT", tmp_path)
    with pytest.raises(run.RunError, match="program is missing"):
        run.import_program()


def test_every_cell_names_files_that_exist():
    bench = run.json.loads((run.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell["config_data"]["name"] == w["config"]
        assert cell["traffic_data"]["name"] == w["traffic"]
        assert (run.HERE / "refs" /
                f"{cell['config_data']['kind']}.py").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (run.HERE / "metrics" / f"{m['name']}.py").is_file()
    for c in bench["configs"]:
        assert (run.ROOT / c["file"]).is_file()
