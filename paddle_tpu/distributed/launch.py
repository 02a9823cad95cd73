"""Process launcher.

Parity with /root/reference/python/paddle/distributed/launch.py and
fleet/launch_utils.py (Cluster :31, Pod :138, start_local_trainers :351,
watch_local_trainers :418): spawns one worker process per host (TPU chips
within a host are all driven by one process — unlike the reference's
process-per-GPU), wires PADDLE_* env vars, supervises children, and kills
the job when any worker dies.

A chip belongs to one process at a time and nothing here confines a
child to a device, so ``--nproc_per_node > 1`` on one host is a CPU
drill (``JAX_PLATFORMS=cpu``): on a TPU host every rank would try to
own every chip.

Beyond the reference's abort-on-any-failure policy, ``supervise(...)`` /
``Supervisor`` adds a relaunch loop: a dead trainer is re-exec'd (after
exponential backoff with jitter) while a restart budget lasts, composing
with auto-checkpoint resume so a preempted trainer rejoins at its last
committed epoch. External death signals (a lapsed heartbeat via
``ps.heartbeat.HeartBeatMonitor.attach_supervisor``) feed the same loop
through ``Supervisor.notify_dead``.

CLI: python -m paddle_tpu.distributed.launch --nproc_per_node=1 train.py
     (add --max_restarts=N to supervise with relaunch instead of abort)
"""
from __future__ import annotations

import multiprocessing as mp
import os
import signal
import subprocess
import sys
import threading
import time


def _worker_env(rank, nranks, endpoints):
    env = dict(os.environ)
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nranks),
        "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
        "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
    })
    return env


def _start_one_trainer(rank, nranks, script_args, base_port=6170):
    """Spawn one rank's worker process (shared by the plain launcher and
    the Supervisor so env wiring can never diverge between them)."""
    endpoints = [f"127.0.0.1:{base_port + i}" for i in range(nranks)]
    cmd = [sys.executable] + list(script_args)
    return subprocess.Popen(cmd, env=_worker_env(rank, nranks, endpoints))


def start_local_trainers(nranks, script_args, base_port=6170):
    return [_start_one_trainer(rank, nranks, script_args, base_port)
            for rank in range(nranks)]


def watch_local_trainers(procs, poll_interval=1.0):
    """Abort-all-on-any-failure supervision (launch_utils.py:418)."""
    try:
        while True:
            alive = False
            for p in procs:
                ret = p.poll()
                if ret is None:
                    alive = True
                elif ret != 0:
                    for q in procs:
                        if q.poll() is None:
                            q.send_signal(signal.SIGTERM)
                    raise RuntimeError(
                        f"Trainer pid={p.pid} exited with code {ret}; "
                        "job aborted")
            if not alive:
                return 0
            time.sleep(poll_interval)
    except KeyboardInterrupt:
        for q in procs:
            if q.poll() is None:
                q.send_signal(signal.SIGTERM)
        raise


class RestartBudgetExceeded(RuntimeError):
    """supervise() spent its restart budget; the job stays down."""


class Supervisor:
    """Relaunch-on-death supervision with restart budget + backoff.

    Each rank runs as one child process (``start_fn(rank)`` must return
    a Popen-shaped object: ``poll()``, ``send_signal()``, ``pid``). A
    rank exiting 0 is complete; any other death consumes one unit of the
    shared restart budget and is re-exec'd after a backoff delay. When
    the budget is spent, everything still alive is terminated and
    RestartBudgetExceeded raised. ``start_fn``/``sleep`` injection keeps
    the whole loop exercisable in-process — no real kills needed
    (tests/test_fault_layer.py drives it with scripted fakes).

    ``notify_dead(rank)`` (thread-safe) marks a live-but-hung rank dead —
    the HeartBeatMonitor integration point: a trainer whose heartbeat
    lapsed is SIGTERM'd and relaunched under the same budget.
    """

    def __init__(self, nranks, script_args=None, base_port=6170,
                 max_restarts=3, backoff=None, poll_interval=1.0,
                 start_fn=None, sleep=time.sleep, drain_window=30.0,
                 clock=time.monotonic):
        from ..fault.retry import Backoff

        self.nranks = int(nranks)
        self.max_restarts = int(max_restarts)
        self.poll_interval = float(poll_interval)
        self.drain_window = float(drain_window)
        self._backoff = backoff or Backoff(base=1.0, cap=30.0)
        self._sleep = sleep
        self._clock = clock
        self._lock = threading.Lock()
        self._external_dead = set()
        self._relaunch_listeners = []
        self._stop_requested = False
        self.restarts = 0
        # per-rank restart attribution (stats()): one flapping rank vs.
        # evenly-spread churn are different operational stories even
        # when the shared budget reads the same
        self.restarts_by_rank: dict = {}
        if start_fn is not None:
            self._start_fn = start_fn
        else:
            if script_args is None:
                raise ValueError("need script_args or start_fn")
            self._start_fn = lambda rank: _start_one_trainer(
                rank, self.nranks, script_args, base_port)

    # -- graceful shutdown (SIGTERM forwarding + bounded drain) -------------
    def request_stop(self) -> None:
        """Ask the supervision loop to shut the job down gracefully:
        children get SIGTERM forwarded (their drain/checkpoint-on-term
        handlers run — the serving engine flushes in-flight batches,
        TrainEpochRange commits its snapshot), then a bounded
        ``drain_window`` passes before any straggler is SIGKILLed.
        Safe from a signal handler or another thread."""
        self._stop_requested = True

    def install_signal_forwarding(self, signals=(signal.SIGTERM,)) -> None:
        """Route the given signals (default SIGTERM) into request_stop so
        `kill -TERM <launcher>` drains the whole job instead of orphaning
        children mid-batch. Main-thread only (signal.signal constraint)."""
        for sig in signals:
            try:
                signal.signal(sig, lambda signum, frame:
                              self.request_stop())
            except (ValueError, OSError):
                pass   # non-main thread / unsupported platform

    def _drain(self, procs, done) -> int:
        """Forward SIGTERM to every live child and wait up to
        drain_window for them to exit on their own; whatever is still
        alive past the window is SIGKILLed (counter
        ``supervisor_drain_kills``). Always returns 0 — the operator
        asked for shutdown, and the children got their drain chance."""
        from .. import profiler

        profiler.bump_counter("supervisor_drains")
        live = [p for rank, p in sorted(procs.items())
                if rank not in done and p.poll() is None]
        for p in live:
            try:
                p.send_signal(signal.SIGTERM)
            except Exception:
                pass
        deadline = self._clock() + self.drain_window
        while any(p.poll() is None for p in live) \
                and self._clock() < deadline:
            self._sleep(min(self.poll_interval,
                            max(0.0, deadline - self._clock())))
        kill = getattr(signal, "SIGKILL", signal.SIGTERM)
        for p in live:
            if p.poll() is None:
                profiler.bump_counter("supervisor_drain_kills")
                try:
                    p.send_signal(kill)
                except Exception:
                    pass
                self._await_death(p)
        return 0

    # -- external liveness policy (heartbeat monitor) -----------------------
    def notify_dead(self, rank: int) -> None:
        with self._lock:
            self._external_dead.add(int(rank))

    def on_relaunch(self, fn) -> None:
        """Register ``fn(rank)`` to run on every rank (re)start — the
        heartbeat monitor uses it to refresh the rank's beat so a fresh
        incarnation gets a full timeout of grace before being flagged
        again."""
        self._relaunch_listeners.append(fn)

    def _start_rank(self, rank):
        proc = self._start_fn(rank)
        for fn in self._relaunch_listeners:
            fn(rank)
        # a notify_dead queued while this rank sat in relaunch backoff
        # refers to the PREVIOUS incarnation: drop it, or the fresh
        # process would be SIGTERM'd on the next loop iteration and the
        # budget drained on a healthy job (the listeners above already
        # refreshed the heartbeat, stopping future re-fires)
        with self._lock:
            self._external_dead.discard(rank)
        return proc

    def _take_external_dead(self):
        with self._lock:
            dead, self._external_dead = self._external_dead, set()
            return dead

    def stats(self) -> dict:
        """Operational snapshot: total restarts consumed, the budget,
        and the per-rank attribution (which rank is flapping)."""
        return {"restarts": self.restarts,
                "max_restarts": self.max_restarts,
                "restarts_by_rank": dict(self.restarts_by_rank)}

    @staticmethod
    def _await_death(p, timeout=10):
        waiter = getattr(p, "wait", None)
        if waiter is not None:
            try:
                waiter(timeout=timeout)
            except Exception:
                pass
        return p.poll()

    # -- the loop -----------------------------------------------------------
    def _schedule_relaunch(self, rank, pending):
        """Consume one budget unit and set the rank's relaunch deadline.
        The backoff is a per-rank deadline, not an inline sleep — one
        rank backing off 30s must not stall death-detection (or the
        heartbeat SIGTERM path) for every other rank."""
        from .. import profiler
        from ..fault import injector as _fault

        if self.restarts >= self.max_restarts:
            # run()'s BaseException handler tears down the survivors
            raise RestartBudgetExceeded(
                f"trainer rank={rank} died and the restart budget "
                f"({self.max_restarts}) is spent; job stays down")
        delay = self._backoff.delay(self.restarts)
        self.restarts += 1
        self.restarts_by_rank[rank] = self.restarts_by_rank.get(rank, 0) + 1
        profiler.bump_counter("trainer_relaunches")
        _fault.point("launch.relaunch")
        # the injected clock paces the backoff deadline like _drain's:
        # tests on fake clocks must never real-sleep through a relaunch
        pending[rank] = self._clock() + delay

    def run(self) -> int:
        procs = {}
        done = set()
        pending = {}   # rank -> monotonic deadline of its relaunch
        try:
            for rank in range(self.nranks):
                procs[rank] = self._start_rank(rank)
            while len(done) < self.nranks:
                if self._stop_requested:
                    return self._drain(procs, done)
                now = self._clock()
                for rank in [r for r, t in pending.items() if now >= t]:
                    del pending[rank]
                    procs[rank] = self._start_rank(rank)
                ext = self._take_external_dead()
                for rank in sorted(procs):
                    if rank in done or rank in pending:
                        continue
                    p = procs[rank]
                    ret = p.poll()
                    if ret is None and rank in ext:
                        # hung per the heartbeat: make it really dead,
                        # then treat like any other death. Exit 0 here
                        # is ambiguous (finished during the lapse vs. a
                        # graceful sys.exit(0) SIGTERM handler killed
                        # mid-training) — relaunch: with auto-checkpoint
                        # resume a truly-finished trainer replays zero
                        # epochs and re-exits 0, while counting a killed
                        # one as done would silently lose its work
                        p.send_signal(signal.SIGTERM)
                        ret = self._await_death(p)
                        if ret is None:
                            # ignored SIGTERM: escalate — a relaunch
                            # while the old incarnation lives would run
                            # two processes with the same rank
                            p.send_signal(
                                getattr(signal, "SIGKILL", signal.SIGTERM))
                            ret = self._await_death(p)
                        if ret is None:
                            # unkillable (D-state I/O): do NOT start a
                            # duplicate; retry the kill next iteration
                            self.notify_dead(rank)
                            continue
                        if ret == 0:
                            ret = -signal.SIGTERM
                    if ret is None:
                        continue
                    if ret == 0:
                        done.add(rank)
                        continue
                    self._schedule_relaunch(rank, pending)
                if len(done) < self.nranks:
                    self._sleep(self.poll_interval)
            return 0
        except BaseException:
            # no exit path may orphan a live trainer: a failed relaunch
            # (ENOENT/ENOMEM from start_fn), Ctrl-C, or budget
            # exhaustion all tear the job down before propagating
            for q in procs.values():
                try:
                    if q.poll() is None:
                        q.send_signal(signal.SIGTERM)
                except Exception:
                    pass
            raise


def supervise(nranks, script_args=None, base_port=6170, max_restarts=3,
              backoff=None, poll_interval=1.0, start_fn=None,
              sleep=time.sleep, drain_window=30.0,
              forward_signals=False) -> int:
    """Run ``nranks`` trainers under relaunch supervision (see
    Supervisor). Returns 0 once every rank has exited cleanly; raises
    RestartBudgetExceeded when deaths outrun the budget.
    ``forward_signals=True`` installs the SIGTERM→graceful-drain
    forwarding (children get SIGTERM + a ``drain_window`` to flush/
    checkpoint before any kill)."""
    sup = Supervisor(nranks, script_args=script_args, base_port=base_port,
                     max_restarts=max_restarts, backoff=backoff,
                     poll_interval=poll_interval, start_fn=start_fn,
                     sleep=sleep, drain_window=drain_window)
    if not forward_signals:
        return sup.run()
    # restore the previous handlers on the way out: leaving ours
    # installed would route a later SIGTERM into a finished Supervisor
    # — silently swallowed, making the process unkillable except -9
    prev = {sig: signal.getsignal(sig) for sig in (signal.SIGTERM,)}
    sup.install_signal_forwarding()
    try:
        return sup.run()
    finally:
        for sig, handler in prev.items():
            try:
                signal.signal(sig, handler)
            except (ValueError, OSError):
                pass


def spawn(func, args=(), nprocs=1, join=True, daemon=False, **options):
    """paddle.distributed.spawn parity (multiprocessing-based)."""
    ctx = mp.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        env_patch = {"PADDLE_TRAINER_ID": str(rank),
                     "PADDLE_TRAINERS_NUM": str(nprocs)}

        def target(rank=rank, env_patch=env_patch):
            os.environ.update(env_patch)
            func(*args)

        p = ctx.Process(target=target, daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
        for p in procs:
            if p.exitcode != 0:
                raise RuntimeError(f"spawned process exited {p.exitcode}")
    return procs


def main():
    import argparse

    parser = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    parser.add_argument("--nproc_per_node", type=int, default=1)
    parser.add_argument("--started_port", type=int, default=6170)
    parser.add_argument("--max_restarts", type=int, default=0,
                        help="relaunch dead trainers up to N times "
                             "(0 = reference abort-on-any-failure)")
    parser.add_argument("training_script")
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    script = [args.training_script] + args.training_script_args
    if args.max_restarts > 0:
        sys.exit(supervise(args.nproc_per_node, script,
                           base_port=args.started_port,
                           max_restarts=args.max_restarts,
                           forward_signals=True))
    procs = start_local_trainers(
        args.nproc_per_node, script, base_port=args.started_port)
    sys.exit(watch_local_trainers(procs))


if __name__ == "__main__":
    main()
