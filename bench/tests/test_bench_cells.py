"""The cells of ``BENCHMARK.json``: each pair of configuration and traffic is
given once, every name resolves to its file, and the four-chip cell's mix is
the one-chip mix under a name of its own, served correct on a 4-device mesh
at a small size on the CPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run  # noqa: E402

CELLS = run.load_benchmark()["workloads"]


def test_each_config_and_traffic_pair_is_given_once():
    pairs = [(w["config"], w["traffic"]) for w in CELLS]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", CELLS, ids=[w["name"] for w in CELLS])
def test_cell_names_resolve_to_files(cell):
    configs = {c["name"]: c for c in run.load_benchmark()["configs"]}
    assert (ROOT / configs[cell["config"]]["file"]).is_file()
    assert (ROOT / "bench" / "mixes" / f"{cell['traffic']}.json").is_file()
    assert cell["chips"] in (1, 4)


def test_four_chip_mix_is_the_one_chip_mix():
    assert run.load_mix("star_repeat_4chip") == run.load_mix("star_repeat")
    cell, = [w for w in CELLS if w["name"] == "sf1-star-repeat-4chip"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch-sf1", "star_repeat_4chip", 4)


FOUR_CHIP_RUN = """
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
from bench import run
from bench.tests.test_bench_harness import small_config
bench = run.load_benchmark()
res = run.run_cell(small_config(), run.load_mix("star_repeat_4chip"),
                   chips=4, seed=2**31 + 41, seconds=1.5, trace=False,
                   metrics=bench["end_to_end"], t_process=time.perf_counter())
print(json.dumps(res))
"""


def test_four_chip_mix_runs_correct_on_a_four_device_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", FOUR_CHIP_RUN, str(ROOT)],
                          capture_output=True, text=True, env=env,
                          timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 4
    assert all(c["value"] == 0 for c in res["checks"].values())
