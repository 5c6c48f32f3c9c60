"""The command's contract off the chip."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "vgg11-cifar10.sync-cabac", "--seed", str(2**33 + 1), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    res = _run(ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "TPU" in res.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    res = _run(tmp_path)
    assert res.returncode != 0
    assert res.stdout.strip() == ""


def test_benchmark_json_names_its_files():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        for path in (f"bench/configs/{w['config']}.json",
                     f"bench/mixes/{w['traffic']}.json",
                     f"bench/limits/{w['name']}.json"):
            assert (ROOT / path).is_file(), path
        assert w["chips"] == 1
    for m in bm["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in bm["end_to_end"]}
    for c in bm["configs"]:
        assert (ROOT / c["file"]).is_file()
