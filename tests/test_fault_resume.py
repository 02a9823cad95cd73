"""Fault-injection resume (VERDICT r4 #5): the composed failure story —
kill a trainer mid-epoch, assert the launch supervisor detects it, and
a relaunch resumes from the auto-checkpoint, skipping completed epochs
with loss continuity. (Reference launch_utils.py:418
watch_local_trainers + incubate/checkpoint/auto_checkpoint.py:265.)
"""
import json
import os

import pytest

from paddle_tpu.distributed import launch

pytestmark = pytest.mark.slow

# portable repo root (the subprocess env replaces PYTHONPATH; it must
# still find paddle_tpu from any checkout)
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "_fault_resume_worker.py")


def _read(log):
    with open(log) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def test_kill_detect_resume_cycle(tmp_path, monkeypatch):
    # subprocess env: CPU backend
    monkeypatch.setenv("PYTHONPATH", _REPO)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("PADDLE_AUTO_CHECKPOINT_PATH",
                       str(tmp_path / "ckpt"))

    # ---- run 1: crash mid-epoch-2 ------------------------------------
    log1 = tmp_path / "run1.jsonl"
    monkeypatch.setenv("FAULT_LOG", str(log1))
    monkeypatch.setenv("KILL_AT_EPOCH", "2")
    procs = launch.start_local_trainers(1, [_WORKER], base_port=6370)
    # the supervisor must DETECT the failure and abort the job
    with pytest.raises(RuntimeError, match="exited with code 17"):
        launch.watch_local_trainers(procs, poll_interval=0.2)
    rows1 = _read(log1)
    assert [r["epoch"] for r in rows1] == [0, 1], (
        "run 1 must complete (and checkpoint) exactly epochs 0-1 before "
        f"the injected crash: {rows1}")
    assert rows1[0]["restored"] == -1      # fresh start

    # ---- run 2: relaunch, resume -------------------------------------
    log2 = tmp_path / "run2.jsonl"
    monkeypatch.setenv("FAULT_LOG", str(log2))
    monkeypatch.setenv("KILL_AT_EPOCH", "-1")
    procs = launch.start_local_trainers(1, [_WORKER], base_port=6370)
    assert launch.watch_local_trainers(procs, poll_interval=0.2) == 0
    rows2 = _read(log2)
    # completed epochs are SKIPPED: resume starts at the crashed epoch
    assert [r["epoch"] for r in rows2] == [2, 3, 4, 5], rows2
    assert rows2[0]["restored"] == 1       # meta said epoch 1 done
    # loss continuity: restored params continue the descent — the first
    # resumed loss is below run 1's last checkpointed loss, and the
    # job keeps converging
    assert rows2[0]["loss"] < rows1[-1]["loss"]
    assert rows2[-1]["loss"] < rows2[0]["loss"]
