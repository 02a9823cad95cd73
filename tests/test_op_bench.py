"""tools/op_bench.py runs end to end on the CPU at its smoke shapes and
prints one parseable row an op. No timing is compared: what a kernel
costs is a chip measurement."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_op_bench_prints_a_row_per_op():
    ops = ["matmul", "softmax_xent"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_SMOKE="1",
               PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "op_bench.py"),
         "--ops", ",".join(ops)],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]
    assert len(rows) == len(ops), out.stdout
    for name, row in zip(ops, rows):
        # a bench that raised leaves {"op", "error"} and no "ms"
        assert row["op"].startswith(name) and "error" not in row, row
        assert row["ms"] > 0 and row["backend"] == "cpu", row
