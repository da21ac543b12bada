"""Importing the benchmark loads no TPU library and describes no chip."""
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent

CODE = f"""
import sys
sys.path[:0] = [{str(BENCH)!r}, {str(BENCH.parent / 'src')!r}]
import run, loadgen, trace_reduce, work
from reference import qwen3, tap_add
for d in sorted((run.BENCH / "drivers").glob("*.py")):
    run.load_module(d)
for m in sorted((run.BENCH / "metrics").glob("*.py")):
    run.load_module(m)
from jax._src import xla_bridge
assert not xla_bridge._backends, xla_bridge._backends
maps = open("/proc/self/maps").read()
assert "libtpu.so" not in maps
print("clean")
"""


def test_import_touches_no_chip():
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "clean"


def test_every_metric_and_cell_has_its_files():
    import run
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert hasattr(run.load_module(run.metric_file(m["name"])), "read")
    for w in bench["workloads"]:
        files = run.cell_files(bench, w["name"])
        assert files["end_to_end"] and files["per_layer"]
        assert hasattr(files["driver"], "window")


@pytest.mark.parametrize("name,file", [
    ("device_idle.vec_long", "device_idle.py"),
    ("kernel_roofline.serve", "kernel_roofline.py"),
    ("launches_per_job.vec", "launches_per_job.vec.py"),
    ("vec_rows_per_s", "vec_rows_per_s.py"),
])
def test_metric_file_falls_back_to_the_quantity(name, file):
    import run
    assert run.metric_file(name) == run.BENCH / "metrics" / file


def test_no_chip_exits_2_without_a_result(capsys):
    import run
    assert run.main(["--workload", "tap-add-r3w20.long", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""
