#!/usr/bin/env python
"""Deterministic chaos drills: elastic kill/resume (ISSUE 7),
parameter-server kill-a-primary (ISSUE 8, ``--ps``), and fleet decode
serving kill-an-engine (ISSUE 17, ``--fleet``).

All three start several JAX processes on one host, so they are CPU
drills (children default to ``JAX_PLATFORMS=cpu``): a chip belongs to
one process at a time and nothing here confines a child to a device.

Fleet drill (``--fleet``): N decode engines come up as subprocesses,
each behind its ``DecodeEngineServer`` HTTP surface; a ``FleetRouter``
sprays deterministic traffic over them, then SIGKILLs the engine a
probe session is pinned to — mid-generation, under live load. The
router's health gate flips the victim out, its chunked
retry-with-failover replays every stranded session on a survivor
(emitted tokens folded into the prompt), and the drill asserts: zero
lost, zero doubled, every output BITWISE equal to the never-killed
dense oracle; ``/readyz`` flipped; the parent's flight-recorder dump
names the killed endpoint. The KV-migration legs then run against a
survivor: a ``PrefillWorker`` ships int8 page frames (adopt +
prefix-hit + dedupe on re-ship + typed malformed reject), the
dead-endpoint ship exercises the ``kv_migration_fallbacks`` degrade
leg with the request still serving, ship-vs-recompute is gated at a
serving-scale config, and a multi-endpoint ``slo_check`` over every
surviving ``/metrics`` must come back healthy.

PS drill (``--ps``): a KVServer comes up in-process; one 2-replica
group serves shard 0 — primary A as a SUPERVISED SUBPROCESS
(``launch.Supervisor``, the real relaunch path), backup B in-process.
The parent is the trainer: it pushes a deterministic gradient stream
through a replicated ``PSClient``. ``PADDLE_FAULT_SPEC=
ps.apply:1@K:SystemExit`` (armed only in A's env) kills A at its
(K+1)-th applied write — mid-stream, with snapshots already committed.
The ReplicaCoordinator observes A's lease expiry, promotes B (shard-map
epoch bump); the client fails over with typed errors only and REPLAYS
the in-flight push (write dedup makes the replay exactly-once); the
supervisor relaunches A, which restores its newest valid SnapshotStore
snapshot and catches up from B's delta log, rejoining as a backup. The
drill asserts: the final pull is BITWISE identical to the never-killed
reference (a local same-backend oracle table fed the same stream — in
sync replication mode zero updates may be lost or doubled), a promotion
and a failover really happened, the relaunched replica reconverged
(digest parity across the group), and the ``ps_*`` counter table.
"""
from __future__ import annotations

_ELASTIC_DOC = """Deterministic elastic-training chaos drill (ISSUE 7 crown test).

Promotes the PR 2 chaos recipe (arm a ``PADDLE_FAULT_SPEC``, supervise,
resume) to a tool that drives the WHOLE elastic story end to end with
real processes and real kills:

1. a KVServer comes up in-process; ``nranks`` trainer workers launch
   under ``launch.Supervisor`` relaunch supervision;
2. every worker rendezvous through ``distributed.elastic.ElasticAgent``
   into generation 0, holds a heartbeat lease, trains the same
   deterministic toy job with ``TrainEpochRange`` mid-epoch
   checkpointing, and barriers each epoch end;
3. ``PADDLE_FAULT_SPEC=drill.step:1@K:SystemExit`` kills ``kill_rank``
   mid-epoch at its (K+1)-th batch (the env spec re-arms per process;
   ``@after`` is what lets the relaunched incarnation run past it);
4. survivors observe the lease expiry as a typed ``WorkerLost``, bump
   the generation, and reform; the supervisor relaunches the dead rank,
   which resumes AT THE EXACT NEXT BATCH from its mid-epoch snapshot
   and rejoins the bumped generation;
5. the drill asserts the killed rank's final loss is **bitwise
   identical** to the never-killed rank 0's (both run the same
   deterministic schedule, so rank 0 *is* the uninterrupted run), that
   a generation bump really happened, and that exactly the expected
   relaunches were spent — then prints the counter table.

Usage::

    JAX_PLATFORMS=cpu python tools/chaos_drill.py [--workdir DIR]
        [--epochs 3] [--batches 4] [--kill-after 6] [--lease-ttl 3]

Exit code 0 = drill passed (bitwise parity + generation bump); the
counter table goes to stdout either way. ``--no-kill`` runs the same
job without the fault spec (a clean baseline of the harness itself).
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


# ---------------------------------------------------------------------------
# worker (runs in the supervised subprocesses)
# ---------------------------------------------------------------------------

def worker_main() -> int:
    import numpy as np

    import paddle_tpu.static as static
    from paddle_tpu import fault, profiler
    from paddle_tpu.distributed.elastic import ElasticAgent
    from paddle_tpu.incubate.checkpoint.auto_checkpoint import (
        TrainEpochRange,
    )

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    endpoint = os.environ["PADDLE_ELASTIC_ENDPOINT"]
    epochs = int(os.environ["DRILL_EPOCHS"])
    batches = int(os.environ["DRILL_BATCHES"])
    save_every = int(os.environ["DRILL_SAVE_EVERY"])
    kill_rank = int(os.environ.get("DRILL_KILL_RANK", "-1"))
    lease_ttl = float(os.environ.get("DRILL_LEASE_TTL", "3.0"))
    log_path = os.environ["DRILL_LOG"]
    h, b = 8, 8

    def log(kind, **fields):
        with open(log_path, "a") as f:
            f.write(json.dumps({"kind": kind, "rank": rank, **fields})
                    + "\n")

    main, startup = static.Program(), static.Program()
    main.random_seed = startup.random_seed = 1234
    with static.program_guard(main, startup):
        x = static.data("x", [-1, h])
        label = static.data("label", [-1, 1], dtype="int64")
        hid = static.nn.fc(x, 16, act="relu")
        hid = static.dropout(hid, dropout_prob=0.2)
        logits = static.nn.fc(hid, 4)
        loss = static.mean(static.softmax_with_cross_entropy(logits, label))
        static.SGD(0.05).minimize(loss)

    exe = static.Executor()
    exe.run(startup)
    cp = static.CompiledProgram(main)
    tr = TrainEpochRange(epochs, name=f"drill_r{rank}",
                         save_every_steps=save_every)
    tr.register(executor=exe, program=main)
    log("start", restored_epoch=tr.restored_epoch,
        restored_batch=tr.restored_batch, exe_step=exe._step)

    agent = ElasticAgent(endpoint, rank, world, job="drill",
                         lease_ttl=lease_ttl)
    agent.join(timeout=240.0)
    agent.start_heartbeat()

    def reader(epoch):
        def gen():
            for i in range(batches):
                rng = np.random.RandomState(epoch * 100 + i)
                yield {"x": rng.randn(b, h).astype(np.float32),
                       "label": rng.randint(0, 4, (b, 1)).astype(np.int64)}
        return gen

    last = None
    for epoch in tr.get():
        for i, batch in tr.steps(epoch, reader(epoch)):
            if rank == kill_rank:
                # the armed PADDLE_FAULT_SPEC decides which visit dies
                fault.point("drill.step")
            out = exe.run(cp, feed=batch, fetch_list=[loss])
            last = np.ravel(out[0]).astype(np.float32)
            log("batch", epoch=epoch, batch=i, step=exe._step - 1,
                loss=float(last[0]))
        agent.synchronize(f"epoch{epoch}", timeout=240.0, max_reforms=3)
    agent.stop_heartbeat()

    counters = {k: v for k, v in profiler.counters_snapshot().items()
                if k in profiler.ELASTIC_COUNTER_NAMES
                or k in profiler.FAULT_COUNTER_NAMES}
    log("final", loss=float(last[0]), loss_hex=last.tobytes().hex(),
        generation=agent.generation, counters=counters)
    return 0


# ---------------------------------------------------------------------------
# the drill (parent process)
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _read_log(path):
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def _flightrec_dir(workdir: str) -> str:
    return os.path.join(workdir, "flightrec")


def _clean_flightrec(workdir: str) -> None:
    d = _flightrec_dir(workdir)
    if os.path.isdir(d):
        for fn in os.listdir(d):
            if fn.startswith("flightrec_"):
                os.remove(os.path.join(d, fn))


def _flightrec_report(workdir: str, error_name: str = "SystemExit") -> dict:
    """Scan the drill's flight-recorder dumps: the postmortem contract
    is that a killed process left a dump whose LAST recorded events
    name the typed error that killed it."""
    d = _flightrec_dir(workdir)
    dumps = []
    if os.path.isdir(d):
        for fn in sorted(os.listdir(d)):
            if fn.startswith("flightrec_") and fn.endswith(".json"):
                try:
                    with open(os.path.join(d, fn)) as f:
                        dumps.append(json.load(f))
                except (OSError, ValueError):
                    pass
    names_killer = any(
        ev.get("error") == error_name
        for dump in dumps for ev in dump.get("events", [])[-3:])
    return {"dumps": len(dumps),
            "reasons": [dump.get("reason") for dump in dumps],
            "names_killer": names_killer}


def run_drill(workdir: str, nranks: int = 2, epochs: int = 3,
              batches: int = 4, save_every: int = 2, kill_rank: int = 1,
              kill_after: int = 6, max_restarts: int = 2,
              lease_ttl: float = 3.0, kill: bool = True) -> dict:
    """Run the drill; returns a report dict (see keys in `main`).

    ``kill_after=K`` kills ``kill_rank`` at its (K+1)-th training batch
    — pick K so the death lands mid-epoch and the relaunched
    incarnation has fewer than K batches left (the re-armed env spec
    then never re-fires, per the ``@after`` skip count).

    ``PADDLE_CHAOS_LEASE_TTL`` overrides ``lease_ttl``: a 3s lease is
    proven-stable on an idle box, but under full-suite load the first
    ``exe.run`` trace holds the GIL long enough to starve the heartbeat
    thread past the TTL — a spurious expiry on a HEALTHY rank double
    -bumps the generation and flakes the drill. Tests that share the
    box with cold compiles pin the knob instead of editing call sites.
    """
    from paddle_tpu.distributed.http_kv import KVServer
    from paddle_tpu.distributed.launch import Supervisor
    from paddle_tpu.fault.retry import Backoff

    lease_ttl = float(os.environ.get("PADDLE_CHAOS_LEASE_TTL",
                                     lease_ttl))
    os.makedirs(workdir, exist_ok=True)
    port = _free_port()
    srv = KVServer(port)
    srv.start()

    logs = {r: os.path.join(workdir, f"rank{r}.jsonl")
            for r in range(nranks)}
    for p in logs.values():
        if os.path.exists(p):
            os.remove(p)
    _clean_flightrec(workdir)

    def env_for(rank):
        env = dict(os.environ)
        env.update({
            "PYTHONPATH": _REPO,
            "JAX_PLATFORMS": env.get("JAX_PLATFORMS", "cpu"),
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(nranks),
            "PADDLE_ELASTIC_ENDPOINT": f"127.0.0.1:{port}",
            "PADDLE_AUTO_CHECKPOINT_PATH": os.path.join(workdir, "ckpt"),
            "DRILL_EPOCHS": str(epochs),
            "DRILL_BATCHES": str(batches),
            "DRILL_SAVE_EVERY": str(save_every),
            "DRILL_KILL_RANK": str(kill_rank if kill else -1),
            "DRILL_LEASE_TTL": repr(lease_ttl),
            "DRILL_LOG": logs[rank],
            # every worker dumps a crash postmortem here; the report
            # asserts the killed rank's dump names the SystemExit
            "PADDLE_FLIGHTREC_DIR": _flightrec_dir(workdir),
        })
        if kill:
            env["PADDLE_FAULT_SPEC"] = (
                f"drill.step:1@{kill_after}:SystemExit")
        else:
            env.pop("PADDLE_FAULT_SPEC", None)
        return env

    def start_fn(rank):
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker"],
            env=env_for(rank))

    # relaunch backoff WIDER than the lease TTL: the drill exercises the
    # lease-expiry -> WorkerLost -> generation-bump path, and a relaunch
    # that re-leases the same (generation, rank) key before the TTL
    # sweep observes the gap reads as continuity — the survivors never
    # reform and the bump assertion goes flaky (the same
    # relaunch-beats-the-sweep race the PS coordinator closes with lease
    # incarnation tokens; here the relaunch hook IS a kill switch, so
    # the deterministic fix is the drill's own backoff policy)
    sup = Supervisor(nranks, start_fn=start_fn,
                     max_restarts=max_restarts,
                     backoff=Backoff(base=float(lease_ttl) + 1.0,
                                     factor=2.0, jitter=0),
                     poll_interval=0.2)
    from paddle_tpu.distributed.launch import RestartBudgetExceeded

    t0 = time.monotonic()
    try:
        rc = sup.run()
    except RestartBudgetExceeded as e:
        # deaths outran the budget: still report (the counter table is
        # the point of a failed drill), just never as "ok"
        print(f"chaos drill: {e}", file=sys.stderr)
        rc = -1
    finally:
        srv.stop()
    wall = time.monotonic() - t0

    rows = {r: _read_log(p) for r, p in logs.items()}
    finals = {r: [row for row in rs if row["kind"] == "final"]
              for r, rs in rows.items()}
    starts = {r: [row for row in rs if row["kind"] == "start"]
              for r, rs in rows.items()}
    report = {
        "rc": rc,
        "wall_s": round(wall, 1),
        "supervisor": sup.stats(),
        "loss_hex": {r: (f[-1]["loss_hex"] if f else None)
                     for r, f in finals.items()},
        "loss": {r: (f[-1]["loss"] if f else None)
                 for r, f in finals.items()},
        "generation": {r: (f[-1]["generation"] if f else None)
                       for r, f in finals.items()},
        "counters": {r: (f[-1]["counters"] if f else {})
                     for r, f in finals.items()},
        "resume": {r: [{k: s[k] for k in
                        ("restored_epoch", "restored_batch", "exe_step")}
                       for s in starts[r]] for r in rows},
        "batches_trained": {r: sum(1 for row in rs
                                   if row["kind"] == "batch")
                            for r, rs in rows.items()},
    }
    hexes = [h for h in report["loss_hex"].values() if h]
    report["parity_bitwise"] = (len(hexes) == nranks
                                and len(set(hexes)) == 1)
    report["generation_bumped"] = any(
        (g or 0) > 0 for g in report["generation"].values())
    report["flightrec"] = _flightrec_report(workdir)
    survivor = next((r for r in range(nranks) if r != kill_rank), 0)
    report["ok"] = bool(
        rc == 0 and report["parity_bitwise"]
        and (not kill or (report["generation_bumped"]
                          and sup.stats()["restarts_by_rank"]
                          .get(kill_rank, 0) >= 1
                          and report["counters"][survivor]
                          .get("worker_lost", 0) >= 1
                          # postmortem contract: the killed rank left a
                          # flight-recorder dump naming its killer
                          and report["flightrec"]["dumps"] >= 1
                          and report["flightrec"]["names_killer"])))
    return report


def _print_table(report: dict) -> None:
    print(f"\nchaos drill: rc={report['rc']} wall={report['wall_s']}s "
          f"supervisor={report['supervisor']}")
    print(f"{'rank':>4} {'final loss':>12} {'loss hex':>10} "
          f"{'gen':>4} {'batches':>8}  resume")
    for r in sorted(report["loss"]):
        print(f"{r:>4} {report['loss'][r]!r:>12} "
              f"{report['loss_hex'][r] or '-':>10} "
              f"{report['generation'][r] if report['generation'][r] is not None else '-':>4} "
              f"{report['batches_trained'][r]:>8}  {report['resume'][r]}")
    names = sorted({k for c in report["counters"].values() for k in c})
    if names:
        print(f"\n{'counter':<24}" + "".join(
            f"rank{r:>2} " for r in sorted(report["counters"])))
        for n in names:
            print(f"{n:<24}" + "".join(
                f"{report['counters'][r].get(n, 0):>6} "
                for r in sorted(report["counters"])))
    print(f"flightrec={report.get('flightrec')}")
    print(f"\nparity_bitwise={report['parity_bitwise']} "
          f"generation_bumped={report['generation_bumped']} "
          f"ok={report['ok']}")


# ---------------------------------------------------------------------------
# the PS drill (ISSUE 8): kill-a-primary, promote, fail over, rejoin
# ---------------------------------------------------------------------------

def ps_server_main() -> int:
    """Supervised pserver subprocess: env-driven replicated bootstrap
    (restore + rejoin happen inside run_server)."""
    from paddle_tpu.ps.server import run_server

    run_server(block=True)
    return 0


def _push_stream(dim: int, pushes: int, rows: int):
    """The deterministic gradient stream both the drill and its oracle
    consume: (ids, grads, lr) per push."""
    import numpy as np

    for i in range(pushes):
        rng = np.random.RandomState(1000 + i)
        ids = rng.randint(0, 200, (rows,)).astype(np.int64)
        grads = rng.randn(rows, dim).astype(np.float32)
        yield ids, grads, 0.05


def run_ps_drill(workdir: str, dim: int = 8, pushes: int = 12,
                 rows: int = 16, kill_after: int = 5,
                 snapshot_every: int = 3, lease_ttl: float = 3.0,
                 max_restarts: int = 1, sync: bool = True,
                 kill: bool = True, rejoin_wait: float = 60.0) -> dict:
    """Run the kill-a-primary drill; returns a report dict.

    ``kill_after=K`` kills the primary at its (K+1)-th applied write.
    Pick K inside [snapshot_every, pushes) so the death lands mid-stream
    with at least one snapshot committed. The re-armed env spec in the
    relaunched process never re-fires: the relaunch rejoins as a BACKUP,
    and backups apply forwards through the replication channel, which
    bypasses the ``ps.apply`` client-write fault point.
    """
    import threading

    import numpy as np

    from paddle_tpu import profiler
    from paddle_tpu.distributed.http_kv import KVClient, KVServer
    from paddle_tpu.distributed.launch import Supervisor
    from paddle_tpu.fault.retry import Backoff
    from paddle_tpu.ps.replication import (
        ReplicaCoordinator, ReplicatedPSServer, _RawPeer, fetch_shard_map,
        local_digest, verify_replicas,
    )
    from paddle_tpu.ps.service import PSClient, table_digest
    from paddle_tpu.ps.table import SparseTable

    os.makedirs(workdir, exist_ok=True)
    _clean_flightrec(workdir)
    job = "psdrill"
    counters0 = profiler.counters_snapshot()
    kv_port = _free_port()
    kvs = KVServer(kv_port)
    kvs.start()
    kv_ep = f"127.0.0.1:{kv_port}"
    kv = KVClient(kv_ep)

    port_a, port_b = _free_port(), _free_port()
    ep_a, ep_b = f"127.0.0.1:{port_a}", f"127.0.0.1:{port_b}"

    coord = ReplicaCoordinator(kv, job=job, lease_ttl=lease_ttl,
                               interval=0.2, boot_grace=60.0)
    coord.publish([[ep_a, ep_b]], sync=sync)

    mk_table = lambda: {0: SparseTable(dim, optimizer="sgd")}  # noqa: E731
    srv_b = ReplicatedPSServer(
        mk_table(), kv, job=job, port=port_b, lease_ttl=lease_ttl,
        snapshot_dir=os.path.join(workdir, "B"),
        snapshot_every=snapshot_every).start()

    def env_for(rank):
        env = dict(os.environ)
        env.update({
            "PYTHONPATH": _REPO,
            "JAX_PLATFORMS": env.get("JAX_PLATFORMS", "cpu"),
            "PADDLE_PORT": str(port_a),
            "PADDLE_PS_KV_ENDPOINT": kv_ep,
            "PADDLE_PS_JOB": job,
            "PADDLE_PS_TABLES": f"0:{dim}:sgd",
            "PADDLE_PS_SNAPSHOT_DIR": os.path.join(workdir, "A"),
            "PADDLE_PS_SNAPSHOT_EVERY": str(snapshot_every),
            "PADDLE_PS_LEASE_TTL": repr(lease_ttl),
            "PADDLE_PS_SYNC": "1" if sync else "0",
            "PADDLE_PS_EXIT_ON_CRASH": "1",
            "PADDLE_FLIGHTREC_DIR": _flightrec_dir(workdir),
        })
        if kill:
            env["PADDLE_FAULT_SPEC"] = (
                f"ps.apply:1@{kill_after}:SystemExit")
        else:
            env.pop("PADDLE_FAULT_SPEC", None)
        return env

    def start_fn(rank):
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--ps-server"],
            env=env_for(rank))

    sup = Supervisor(1, start_fn=start_fn, max_restarts=max_restarts,
                     backoff=Backoff(base=0.5, factor=2.0, jitter=0),
                     poll_interval=0.2)
    sup_rc = {}

    def sup_run():
        try:
            sup_rc["rc"] = sup.run()
        except BaseException as e:  # noqa: B036 (reported, not masked)
            sup_rc["error"] = repr(e)

    sup_thread = threading.Thread(target=sup_run, daemon=True)
    sup_thread.start()
    coord.start()

    t0 = time.monotonic()
    report = {"ok": False, "kill": kill}
    try:
        # wait for A's first lease (its heavy jax import dominates)
        kv.wait(f"ps/{job}/lease/{ep_a}", timeout=120.0)

        client = PSClient(kv=kv, job=job, failover_timeout=60.0)
        oracle = SparseTable(dim, optimizer="sgd")   # never-killed ref
        touched = set()
        for ids, grads, lr in _push_stream(dim, pushes, rows):
            client.push(0, ids, grads, dim, lr)
            oracle.push(ids, grads, lr)
            touched.update(int(i) for i in ids)

        all_ids = np.array(sorted(touched), np.int64)
        final = client.pull(0, all_ids, dim)
        report["final_digest"] = final.tobytes().hex()[:32]
        report["expected_digest"] = (
            oracle.pull(all_ids).tobytes().hex()[:32])
        report["parity_bitwise"] = (
            report["final_digest"] == report["expected_digest"])
        m = fetch_shard_map(kv, job)
        report["epoch"] = m.epoch
        report["groups"] = m.groups
        report["client_epoch"] = client.epoch

        # the relaunched replica must reconverge: same seq, same digest
        deadline = time.monotonic() + (rejoin_wait if kill else 1.0)
        converged = False
        while time.monotonic() < deadline:
            probe = _RawPeer(ep_a)
            try:
                seq_a, _ = probe.seq_epoch()
            except (ConnectionError, OSError):
                time.sleep(0.3)
                continue
            finally:
                probe.close()
            if seq_a == srv_b.seq:
                converged = True
                break
            time.sleep(0.3)
        report["replicas_converged"] = converged
        report["seq"] = {"A": (seq_a if converged else None),
                         "B": srv_b.seq}
        if converged:
            verify_replicas(m)   # raises ReplicaDiverged on mismatch
            try:
                dig_a = _RawPeer(ep_a).digest(0).hex()
            except (ConnectionError, OSError):
                dig_a = None
            report["digest_parity"] = (
                dig_a == table_digest(srv_b.tables[0]).hex())
        client.stop_heartbeat()
        client.close()
    except BaseException as e:  # noqa: B036 (the report IS the output)
        report["error"] = repr(e)
    finally:
        coord.stop()
        sup.request_stop()
        sup_thread.join(timeout=45)
        srv_b.stop()
        kvs.stop()
    report["wall_s"] = round(time.monotonic() - t0, 1)
    report["supervisor"] = sup.stats()
    report["supervisor_rc"] = sup_rc
    delta = {k: v - counters0.get(k, 0)
             for k, v in profiler.counters_snapshot().items()}
    from paddle_tpu.profiler import PS_COUNTER_NAMES

    report["counters"] = {n: delta.get(n, 0) for n in PS_COUNTER_NAMES}
    report["promotions"] = coord.promotions
    report["flightrec"] = _flightrec_report(workdir)
    report["ok"] = bool(
        "error" not in report
        and report.get("parity_bitwise")
        and report.get("replicas_converged")
        and (not kill or (
            report["counters"]["ps_failovers"] >= 1
            and report["counters"]["ps_promotions"] >= 1
            and report.get("epoch", 1) >= 2
            and report.get("digest_parity")
            and sup.stats()["restarts_by_rank"].get(0, 0) >= 1
            # postmortem contract: the killed primary left a dump
            # whose last events name the injected SystemExit
            and report["flightrec"]["dumps"] >= 1
            and report["flightrec"]["names_killer"])))
    return report


def _print_ps_table(report: dict) -> None:
    print(f"\nps chaos drill: kill={report['kill']} "
          f"wall={report['wall_s']}s supervisor={report['supervisor']}")
    if "error" in report:
        print(f"ERROR: {report['error']}")
    print(f"epoch={report.get('epoch')} groups={report.get('groups')}")
    print(f"final    {report.get('final_digest')}")
    print(f"expected {report.get('expected_digest')}  "
          f"parity_bitwise={report.get('parity_bitwise')}")
    print(f"seq={report.get('seq')} "
          f"replicas_converged={report.get('replicas_converged')} "
          f"digest_parity={report.get('digest_parity')}")
    from tools.metrics_watch import format_counter_table

    print("\n" + format_counter_table(report.get("counters", {}),
                                      name_width=24))
    print(f"flightrec={report.get('flightrec')}")
    print(f"\nok={report['ok']}")


def ps_main(argv) -> int:
    ap = argparse.ArgumentParser(
        description="deterministic PS kill-a-primary chaos drill")
    ap.add_argument("--workdir", default="/tmp/paddle_tpu_ps_drill")
    ap.add_argument("--dim", type=int, default=8)
    ap.add_argument("--pushes", type=int, default=12)
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--kill-after", type=int, default=5)
    ap.add_argument("--snapshot-every", type=int, default=3)
    # 3.0s matches the elastic drill's proven-stable TTL on the noisy
    # 2-core CI box: a shorter lease can expire SPURIOUSLY when the
    # GIL-starved parent delays serving a renewal, promoting the backup
    # before the kill even lands (the drill then exercises the fence
    # path instead of the crash-failover path it asserts)
    ap.add_argument("--lease-ttl", type=float, default=3.0)
    ap.add_argument("--max-restarts", type=int, default=1)
    ap.add_argument("--async-repl", action="store_true",
                    help="async replication (bounded lag) instead of sync")
    ap.add_argument("--no-kill", action="store_true",
                    help="clean baseline: same harness, no fault spec")
    args = ap.parse_args(argv)
    report = run_ps_drill(
        args.workdir, dim=args.dim, pushes=args.pushes, rows=args.rows,
        kill_after=args.kill_after, snapshot_every=args.snapshot_every,
        lease_ttl=args.lease_ttl, max_restarts=args.max_restarts,
        sync=not args.async_repl, kill=not args.no_kill)
    _print_ps_table(report)
    return 0 if report["ok"] else 1


# ---------------------------------------------------------------------------
# the fleet drill (ISSUE 17): SIGKILL a decode engine under live traffic
# ---------------------------------------------------------------------------

def fleet_engine_main() -> int:
    """One fleet member: a decode engine + its HTTP surface, env-driven.
    Lives until SIGTERM (drained by ``install_sigterm_drain`` — the
    zero-lost shutdown) or SIGKILL (the chaos)."""
    from paddle_tpu.inference.decode import DecodeEngine, DecodeModelConfig
    from paddle_tpu.inference.serving import install_sigterm_drain
    from paddle_tpu.serving import DecodeEngineServer

    env = os.environ
    cfg = DecodeModelConfig(
        vocab_size=int(env["FLEET_VOCAB"]),
        n_layers=int(env["FLEET_LAYERS"]),
        n_heads=int(env["FLEET_HEADS"]),
        head_dim=int(env["FLEET_HEAD_DIM"]),
        ffn_dim=int(env["FLEET_FFN"]),
        max_context=int(env["FLEET_PAGES_PER_SEQ"])
        * int(env["FLEET_PAGE_SIZE"]))
    engine = DecodeEngine(
        cfg, seed=int(env["FLEET_SEED"]),
        n_pages=int(env["FLEET_PAGES"]),
        page_size=int(env["FLEET_PAGE_SIZE"]),
        max_pages_per_seq=int(env["FLEET_PAGES_PER_SEQ"]),
        kv_codec=env.get("FLEET_KV_CODEC", "int8"))
    engine.warm()
    engine.start()
    srv = DecodeEngineServer(engine, port=int(env["FLEET_PORT"]))
    srv.start()
    install_sigterm_drain(engine, exit_code=0)
    with open(env["FLEET_LOG"], "a") as f:
        f.write(json.dumps({"kind": "ready", "pid": os.getpid(),
                            "port": srv.port}) + "\n")
    while True:   # the parent owns this process's death
        time.sleep(3600)


def _http_get(endpoint: str, path: str, timeout: float = 2.0):
    """(status, body) — raises OSError family when the port is dead."""
    import http.client

    host, _, port = endpoint.rpartition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _wait_ready(endpoint: str, timeout: float = 180.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            status, _ = _http_get(endpoint, "/readyz")
            if status == 200:
                return True
        except OSError:
            pass
        time.sleep(0.2)
    return False


def _port_dead(endpoint: str, timeout: float = 10.0) -> bool:
    """True once /readyz stops answering 200 — refused OR non-ready."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            status, _ = _http_get(endpoint, "/readyz")
            if status != 200:
                return True
        except OSError:
            return True
        time.sleep(0.1)
    return False


def run_fleet_drill(workdir: str, n_engines: int = 3, requests: int = 9,
                    chunk_tokens: int = 4, kill: bool = True,
                    kv_codec: str = "int8", seed: int = 11) -> dict:
    """SIGKILL one of ``n_engines`` decode engines mid-generation under
    live router traffic; assert the fleet absorbed it with zero lost,
    zero doubled, and every output bitwise equal to the never-killed
    dense oracle. Then run the KV-migration legs against a survivor
    (ship + dedupe + malformed reject + dead-endpoint fallback) and the
    fleet-wide SLO burn gate."""
    import threading

    import numpy as np

    from paddle_tpu import profiler
    from paddle_tpu.inference.decode import (DecodeModelConfig,
                                             init_decode_params,
                                             reference_generate)
    from paddle_tpu.observability.flight_recorder import flight_recorder
    from paddle_tpu.serving import (FleetRouter, HTTPReplica,
                                    MalformedPageFrame, MigrationClient,
                                    PrefillWorker, migration_cost)

    geom = {"FLEET_VOCAB": "64", "FLEET_LAYERS": "2",
            "FLEET_HEADS": "4", "FLEET_HEAD_DIM": "16",
            "FLEET_FFN": "128", "FLEET_PAGES": "64",
            "FLEET_PAGE_SIZE": "8", "FLEET_PAGES_PER_SEQ": "8"}
    cfg = DecodeModelConfig(
        vocab_size=64, n_layers=2, n_heads=4, head_dim=16, ffn_dim=128,
        max_context=64)
    params = init_decode_params(cfg, seed)   # the oracle's weights

    os.makedirs(workdir, exist_ok=True)
    _clean_flightrec(workdir)
    counters0 = profiler.counters_snapshot()
    log_path = os.path.join(workdir, "fleet.jsonl")
    if os.path.exists(log_path):
        os.remove(log_path)

    ports = [_free_port() for _ in range(n_engines)]
    endpoints = [f"127.0.0.1:{p}" for p in ports]

    def env_for(port):
        env = dict(os.environ)
        env.update(geom)
        env.update({
            "PYTHONPATH": _REPO,
            "JAX_PLATFORMS": env.get("JAX_PLATFORMS", "cpu"),
            "FLEET_PORT": str(port),
            "FLEET_SEED": str(seed),
            "FLEET_KV_CODEC": kv_codec,
            "FLEET_LOG": log_path,
            "PADDLE_FLIGHTREC_DIR": _flightrec_dir(workdir),
        })
        env.pop("PADDLE_FAULT_SPEC", None)
        return env

    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--fleet-engine"],
        env=env_for(p)) for p in ports]

    t0 = time.monotonic()
    report: dict = {"ok": False, "kill": kill, "engines": n_engines,
                    "endpoints": endpoints}
    router = None
    try:
        for ep in endpoints:
            if not _wait_ready(ep):
                raise RuntimeError(f"engine {ep} never became ready")
        report["readyz_before"] = True

        router = FleetRouter([HTTPReplica(ep) for ep in endpoints],
                             chunk_tokens=chunk_tokens, config=cfg)

        # --- live traffic: deterministic prompts, zipf-free spread ---
        out_lens = (8, 12, 16)
        prompts = {}
        for i in range(requests):
            rng = np.random.RandomState(i)
            n = (6, 14, 10)[i % 3]
            prompts[i] = [int(t) for t in
                          rng.randint(0, cfg.vocab_size, size=n)]
        results: dict = {}
        errors: dict = {}

        def traffic(i):
            try:
                h = router.submit(prompts[i],
                                  max_new_tokens=out_lens[i % 3],
                                  session=f"s{i:02d}")
                results[i] = h.result(120.0)
            except BaseException as e:  # noqa: B036 (reported below)
                errors[i] = repr(e)

        threads = [threading.Thread(target=traffic, args=(i,),
                                    daemon=True)
                   for i in range(requests)]
        for t in threads:
            t.start()

        # --- the kill: SIGKILL the probe session's pinned engine the
        # moment its first chunk lands (mid-generation by construction)
        probe_rng = np.random.RandomState(999)
        probe_prompt = [int(t) for t in
                        probe_rng.randint(0, cfg.vocab_size, size=12)]
        victim_box: dict = {}
        killed = threading.Event()

        def killer(emitted):
            if kill and not killed.is_set():
                name = router.session_replica("probe")
                victim_box["endpoint"] = name
                procs[endpoints.index(name)].kill()   # SIGKILL, no grace
                killed.set()

        h_probe = router.submit(probe_prompt, max_new_tokens=24,
                                session="probe", on_chunk=killer)
        probe_tokens = h_probe.result(120.0)
        for t in threads:
            t.join(timeout=120.0)

        victim = victim_box.get("endpoint")
        report["victim"] = victim
        if kill:
            report["readyz_flipped"] = _port_dead(victim)

        # --- zero lost, zero doubled, bitwise oracle parity ---
        report["traffic_errors"] = errors
        report["lost"] = sorted(set(range(requests)) - set(results))
        report["probe_len"] = len(probe_tokens)
        probe_oracle = reference_generate(cfg, params, probe_prompt, 24)
        traffic_parity = all(
            results.get(i) == reference_generate(
                cfg, params, prompts[i], out_lens[i % 3])
            for i in range(requests))
        report["parity_bitwise"] = (probe_tokens == probe_oracle
                                    and traffic_parity)

        # --- KV migration legs against a survivor ---
        survivor = next(ep for ep in endpoints if ep != victim)
        report["survivor"] = survivor
        worker = PrefillWorker(cfg, params=params, page_size=8,
                               codec=kv_codec)
        mig_rng = np.random.RandomState(555)
        mig_prompt = [int(t) for t in
                      mig_rng.randint(0, cfg.vocab_size, size=24)]
        shipment = worker.prefill(mig_prompt)
        s_replica = HTTPReplica(survivor)

        def hits(ep):
            _, body = _http_get(ep, "/metrics", timeout=5.0)
            from paddle_tpu.observability.metrics import (
                parse_prometheus_text,
            )
            samples = parse_prometheus_text(body.decode())
            return sum(v for k, v in samples.items()
                       if k.split("{")[0] == "kv_prefix_hits")

        hits0 = hits(survivor)
        mig1 = MigrationClient(s_replica.adopt).migrate(shipment)
        report["migrate"] = {k: mig1.get(k) for k in
                            ("ok", "adopted", "shared", "pages",
                             "frame_bytes", "encoded_bytes",
                             "f32_bytes")}
        mig_tokens = s_replica.generate_chunk(mig_prompt, 8, None)
        report["migrate_parity"] = (
            mig_tokens == reference_generate(cfg, params,
                                             mig_prompt, 8))
        report["migrate_prefix_hits"] = hits(survivor) - hits0
        # shipping the same prefix again must DEDUPE, not duplicate
        mig2 = MigrationClient(s_replica.adopt).migrate(shipment)
        report["migrate_dedupe"] = {
            "adopted": mig2.get("adopted"), "shared": mig2.get("shared")}

        # malformed frame: typed reject at the wire, not a 500
        try:
            s_replica.adopt(shipment.frame[:-3])
            report["malformed_reject"] = False
        except MalformedPageFrame:
            report["malformed_reject"] = True

        # degrade leg: ship at the DEAD endpoint — retries burn, the
        # fallback counter ticks, and the request itself still serves
        # (local recompute; the user never sees the failed migration)
        fb_target = victim if kill else "127.0.0.1:1"
        fb = MigrationClient(HTTPReplica(fb_target).adopt,
                             max_attempts=2).migrate(shipment)
        report["fallback"] = {"ok": fb.get("ok"),
                              "reason": fb.get("reason")}
        fb_rng = np.random.RandomState(556)
        fb_prompt = [int(t) for t in
                     fb_rng.randint(0, cfg.vocab_size, size=16)]
        report["fallback_parity"] = (
            router.generate(fb_prompt, max_new_tokens=8)
            == reference_generate(cfg, params, fb_prompt, 8))

        # --- ship-vs-recompute: the toy model is honest (too small to
        # be worth shipping); the gate runs at a serving-scale shape
        report["cost_toy"] = migration_cost(cfg, len(mig_prompt),
                                            codec=kv_codec)
        serving_cfg = DecodeModelConfig(
            vocab_size=256_000, n_layers=48, n_heads=32, head_dim=128,
            ffn_dim=32_768, max_context=8192)
        report["cost_serving"] = migration_cost(serving_cfg, 2048,
                                                codec=kv_codec)

        # --- fleet-wide SLO burn gate over every surviving /metrics ---
        from tools import slo_check

        scrapes = []
        for ep in endpoints:
            if ep == victim:
                continue
            _, body = _http_get(ep, "/metrics", timeout=5.0)
            path = os.path.join(
                workdir, f"scrape_{ep.replace(':', '_')}.txt")
            with open(path, "w") as f:
                f.write(body.decode())
            scrapes.append(path)
        slo_argv = []
        for p in scrapes:
            slo_argv += ["--metrics", p]
        report["slo_rc"] = slo_check.main(slo_argv)

        # --- postmortem: the router named the kill; dump the ring ---
        os.makedirs(_flightrec_dir(workdir), exist_ok=True)
        flight_recorder().dump(
            reason="fleet_failover",
            path=os.path.join(_flightrec_dir(workdir),
                              f"flightrec_{os.getpid()}.json"))
    except BaseException as e:  # noqa: B036 (the report IS the output)
        report["error"] = repr(e)
    finally:
        if router is not None:
            try:
                router.drain(timeout=10.0)
            except Exception:
                pass
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
    report["wall_s"] = round(time.monotonic() - t0, 1)

    delta = {k: v - counters0.get(k, 0)
             for k, v in profiler.counters_snapshot().items()}
    report["counters"] = {
        n: delta.get(n, 0)
        for n in (*profiler.ROUTER_COUNTER_NAMES, "retry_attempts",
                  "retry_giveups", "kv_migration_fallbacks")}
    if router is not None:
        report["counters"].update(
            {k: v for k, v in router.counters.items()
             if k.startswith("router_")})

    dumps = _flightrec_report(workdir)
    victim = report.get("victim")
    names_kill = False
    d = _flightrec_dir(workdir)
    if os.path.isdir(d) and victim:
        for fn in os.listdir(d):
            if not fn.startswith("flightrec_"):
                continue
            try:
                with open(os.path.join(d, fn)) as f:
                    dump = json.load(f)
            except (OSError, ValueError):
                continue
            if dump.get("reason") == "fleet_failover" and any(
                    ev.get("kind") == "replica_dead"
                    and ev.get("replica") == victim
                    for ev in dump.get("events", [])):
                names_kill = True
    report["flightrec"] = {"dumps": dumps["dumps"],
                           "reasons": dumps["reasons"],
                           "names_kill": names_kill}

    ctr = report["counters"]
    report["ok"] = bool(
        "error" not in report
        and not report.get("lost")
        and not report.get("traffic_errors")
        and report.get("parity_bitwise")
        and report.get("migrate", {}).get("ok")
        and report.get("migrate_parity")
        and report.get("migrate_prefix_hits", 0) >= 1
        and report.get("migrate_dedupe", {}).get("adopted") == 0
        and report.get("migrate_dedupe", {}).get("shared", 0) >= 1
        and report.get("malformed_reject")
        and report.get("fallback", {}).get("ok") is False
        and report.get("fallback_parity")
        and ctr.get("kv_migration_fallbacks", 0) >= 1
        and report.get("cost_serving", {}).get("cheaper_to_ship")
        and report.get("slo_rc") == 0
        and (not kill or (report.get("readyz_flipped")
                          and ctr.get("router_failovers", 0) >= 1
                          and ctr.get("router_replays", 0) >= 1
                          and report["flightrec"]["names_kill"])))
    return report


def _print_fleet_table(report: dict) -> None:
    print(f"\nfleet chaos drill: kill={report['kill']} "
          f"engines={report.get('engines')} wall={report['wall_s']}s")
    if "error" in report:
        print(f"ERROR: {report['error']}")
    print(f"victim={report.get('victim')} "
          f"readyz_flipped={report.get('readyz_flipped')} "
          f"survivor={report.get('survivor')}")
    print(f"lost={report.get('lost')} "
          f"traffic_errors={report.get('traffic_errors')} "
          f"parity_bitwise={report.get('parity_bitwise')}")
    print(f"migrate={report.get('migrate')} "
          f"parity={report.get('migrate_parity')} "
          f"prefix_hits={report.get('migrate_prefix_hits')} "
          f"dedupe={report.get('migrate_dedupe')}")
    print(f"malformed_reject={report.get('malformed_reject')} "
          f"fallback={report.get('fallback')} "
          f"fallback_parity={report.get('fallback_parity')}")
    cost_t, cost_s = report.get("cost_toy", {}), \
        report.get("cost_serving", {})
    print(f"cost: toy cheaper_to_ship={cost_t.get('cheaper_to_ship')} "
          f"({cost_t.get('encoded_bytes')}B vs "
          f"{cost_t.get('flops_equiv_bytes')}B-equiv) | serving-scale "
          f"cheaper_to_ship={cost_s.get('cheaper_to_ship')} "
          f"({cost_s.get('encoded_bytes')}B vs "
          f"{cost_s.get('flops_equiv_bytes')}B-equiv, "
          f"saved {cost_s.get('bytes_saved_pct')}%)")
    print(f"slo_rc={report.get('slo_rc')} "
          f"flightrec={report.get('flightrec')}")
    from tools.metrics_watch import format_counter_table

    print("\n" + format_counter_table(report.get("counters", {}),
                                      name_width=28))
    print(f"\nok={report['ok']}")


def fleet_main(argv) -> int:
    ap = argparse.ArgumentParser(
        description="fleet decode drill: SIGKILL an engine under live "
                    "router traffic; assert failover, bitwise replay "
                    "parity, and the KV-migration legs")
    ap.add_argument("--workdir", default="/tmp/paddle_tpu_fleet_drill")
    ap.add_argument("--engines", type=int, default=3)
    ap.add_argument("--requests", type=int, default=9)
    ap.add_argument("--chunk-tokens", type=int, default=4)
    ap.add_argument("--kv-codec", default="int8",
                    choices=("off", "int8"))
    ap.add_argument("--no-kill", action="store_true",
                    help="clean baseline: same traffic, no SIGKILL")
    args = ap.parse_args(argv)
    report = run_fleet_drill(
        args.workdir, n_engines=args.engines, requests=args.requests,
        chunk_tokens=args.chunk_tokens, kv_codec=args.kv_codec,
        kill=not args.no_kill)
    _print_fleet_table(report)
    return 0 if report["ok"] else 1


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "--worker":
        return worker_main()
    if argv and argv[0] == "--ps-server":
        return ps_server_main()
    if argv and argv[0] == "--fleet-engine":
        return fleet_engine_main()
    if argv and argv[0] == "--ps":
        return ps_main(argv[1:])
    if argv and argv[0] == "--fleet":
        return fleet_main(argv[1:])
    ap = argparse.ArgumentParser(
        description="deterministic elastic kill/resume chaos drill")
    ap.add_argument("--workdir", default="/tmp/paddle_tpu_chaos_drill")
    ap.add_argument("--nranks", type=int, default=2)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--save-every", type=int, default=2)
    ap.add_argument("--kill-rank", type=int, default=1)
    ap.add_argument("--kill-after", type=int, default=6)
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--lease-ttl", type=float, default=3.0)
    ap.add_argument("--no-kill", action="store_true",
                    help="clean baseline: same job, no fault spec")
    args = ap.parse_args(argv)
    report = run_drill(args.workdir, nranks=args.nranks,
                       epochs=args.epochs, batches=args.batches,
                       save_every=args.save_every,
                       kill_rank=args.kill_rank,
                       kill_after=args.kill_after,
                       max_restarts=args.max_restarts,
                       lease_ttl=args.lease_ttl, kill=not args.no_kill)
    _print_table(report)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
