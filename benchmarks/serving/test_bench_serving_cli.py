"""On a CPU the benchmark refuses to run: it exits nonzero and prints no
result (a CPU number is never a device number)."""
import os
import pathlib
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent


def _bench(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(HERE / "bench.py"), *args],
                          capture_output=True, text=True, env=env,
                          timeout=300)


def test_cpu_run_exits_nonzero_with_no_result():
    p = _bench("--workload", "phi3_mini.decode_heavy", "--seed",
               str(2**40 + 3), "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs 1 TPU chip" in p.stderr


def test_unknown_workload_exits_nonzero():
    p = _bench("--workload", "no_such.cell", "--seed", "1", "--seconds",
               "1", "--trace", "1")
    assert p.returncode != 0
    assert "{" not in p.stdout
