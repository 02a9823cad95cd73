"""PS at-scale micro-bench (VERDICT r4 #7): a >=1M-row sparse table
sharded over TWO PSServer PROCESSES — pull and push throughput plus
the geo-delta path, each held to a sanity floor. (Reference
operators/distributed/large_scale_kv.h — large-scale KV is exactly the
capability this measures.)
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

pytestmark = pytest.mark.slow

# portable repo root (the subprocess env replaces PYTHONPATH; it must
# still find paddle_tpu from any checkout)
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "_ps_server_worker.py")

DIM = 16
ROWS = 1_000_000
BATCH = 100_000


@pytest.fixture
def two_server_procs():
    env = dict(os.environ)
    env.update(PYTHONPATH=_REPO, JAX_PLATFORMS="cpu",
               PS_DIM=str(DIM))
    procs, endpoints = [], []
    for _ in range(2):
        p = subprocess.Popen([sys.executable, _WORKER], env=env,
                             stdout=subprocess.PIPE, text=True)
        procs.append(p)
        line = p.stdout.readline().strip()
        assert line.startswith("ENDPOINT "), line
        endpoints.append(line.split()[1])
    yield endpoints
    for p in procs:
        p.terminate()
    for p in procs:
        p.wait(timeout=10)


def test_million_row_sharded_pull_push_throughput(two_server_procs):
    from paddle_tpu.ps.service import PSClient

    client = PSClient(two_server_procs)
    ids_all = np.arange(ROWS, dtype=np.int64)
    grads = np.ones((BATCH, DIM), np.float32) * 0.01

    # pull 1M rows in batches (rows materialize server-side on first
    # touch, like large_scale_kv's on-demand init)
    t0 = time.perf_counter()
    first = None
    for s in range(0, ROWS, BATCH):
        out = client.pull(0, ids_all[s:s + BATCH], DIM)
        if first is None:
            first = out
    pull_dt = time.perf_counter() - t0
    assert first.shape == (BATCH, DIM)

    t0 = time.perf_counter()
    for s in range(0, ROWS, BATCH):
        client.push(0, ids_all[s:s + BATCH], grads, DIM, lr=0.1)
    push_dt = time.perf_counter() - t0

    # the push must have actually trained the rows
    after = client.pull(0, ids_all[:4], DIM)
    np.testing.assert_allclose(after, first[:4] - 0.1 * 0.01, atol=1e-6)

    pull_tput = ROWS / pull_dt
    push_tput = ROWS / push_dt
    # sanity floor: loopback TCP + native KV should stream well over
    # 100k rows/s; a 10x regression would trip this
    assert pull_tput > 5e4 and push_tput > 5e4, (pull_dt, push_dt)


def test_geo_delta_throughput(two_server_procs):
    from paddle_tpu.ps.communicator import GeoCommunicator
    from paddle_tpu.ps.service import PSClient
    from paddle_tpu.ps.table import SparseTable

    client = PSClient(two_server_procs)
    local = SparseTable(dim=DIM, init_range=0.01, seed=2)
    geo = GeoCommunicator(client, local, table_id=0, k_steps=2)
    rng = np.random.RandomState(0)
    n_rounds, ids_per_round = 20, 20_000
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        ids = rng.randint(0, ROWS, ids_per_round).astype(np.int64)
        geo.snapshot(ids)
        vals = local.pull(ids)
        local.assign(ids, vals - 0.01)       # fake local training delta
        geo.step()
    geo.sync()
    dt = time.perf_counter() - t0
    tput = n_rounds * ids_per_round / dt
    assert tput > 1e4, dt
