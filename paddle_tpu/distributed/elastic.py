"""Elastic fault-tolerant launch: supervised workers with restart + heartbeat.

Reference: python/paddle/distributed/fleet/elastic (ElasticManager watching
etcd for node flaps and relaunching trainers) and launch_utils.py
watch_local_trainers — the reference treats a dead trainer as a pod-fatal
event; at TPU-pod scale ("Scale MLPerf-0.6 models on Google TPU-v3 Pods",
arXiv:1909.09756) preemption and transient flakiness are the NORMAL case,
so the supervisor here restarts crashed workers with capped exponential
backoff + jitter instead of tearing the job down.

Recovery is step-accurate, not epoch-0: a restarted worker re-enters
training through `AutoCheckpointManager.restore_latest()` (the manager's
`train_step_range`/`train_epoch_range` do this automatically), so the
restart window is bounded by `save_every_n_steps`.

Hang detection is heartbeat-based: each worker incarnation gets a private
heartbeat file (env `PADDLE_ELASTIC_HEARTBEAT_FILE`); the training loop
touches it via `elastic.heartbeat()` each time a step ends (wired into the
checkpoint manager's step/epoch ranges after the step and its save, so
supervised jobs get it for free). A beat marks a finished step. A worker
whose heartbeat goes stale past `heartbeat_timeout` is killed and restarted
through the same backoff path. The timeout is only enforced once the first
beat lands, i.e. once the incarnation's first step has ended: start-up
(imports, restore, the first step's compile) can legitimately take longer
than a steady-state step and is not judged.
"""
from __future__ import annotations

import os
import random
import signal
import subprocess
import sys
import time
from typing import Callable, List, Optional, Sequence, Union

__all__ = ["BackoffPolicy", "ElasticSupervisor", "ElasticJobError",
           "WorkerSpec", "elastic_spawn", "heartbeat"]

# env contract (in addition to the PADDLE_TRAINER_* launch contract)
HEARTBEAT_FILE_ENV = "PADDLE_ELASTIC_HEARTBEAT_FILE"
RESTART_COUNT_ENV = "PADDLE_ELASTIC_RESTART_COUNT"
MAX_RESTARTS_ENV = "PADDLE_ELASTIC_MAX_RESTARTS"


def heartbeat():
    """Touch this worker's heartbeat file (no-op outside a supervised run).

    A beat marks a finished step: AutoCheckpointManager's ranges call it
    once each step/epoch has ended (after its save). The supervisor does
    not judge a worker until its first beat, so the first step (and its
    compile) counts as start-up. Custom loops should beat at the end of
    each step, and long steps at least once per `heartbeat_timeout`.
    """
    path = os.environ.get(HEARTBEAT_FILE_ENV)
    if not path:
        return
    try:
        with open(path, "a"):
            os.utime(path, None)
    except OSError:
        pass  # a beat lost to fs flakiness must never kill the step


class BackoffPolicy:
    """Capped exponential restart backoff with seeded multiplicative
    jitter: delay(n) = min(max_delay, base * factor**n) * (1 + U[0,
    jitter)). The SAME policy object serves both restart supervisors in
    the system — the trainer-level ElasticSupervisor below and the
    serving replica supervisor (inference/serving/replica.py) — so a
    correlated failure of many workers/replicas never produces a
    synchronized restart storm in either runtime."""

    def __init__(self, base: float = 0.25, factor: float = 2.0,
                 max_delay: float = 30.0, jitter: float = 0.25,
                 seed: Optional[int] = None):
        self.base = float(base)
        self.factor = float(factor)
        self.max_delay = float(max_delay)
        self.jitter = float(jitter)
        self._rng = random.Random(seed)

    def delay(self, n_prev_restarts: int) -> float:
        """Delay before restart #(n_prev_restarts+1) of one worker."""
        d = self.base * (self.factor ** n_prev_restarts)
        d = min(d, self.max_delay)
        return d * (1.0 + self.jitter * self._rng.random())


class ElasticJobError(RuntimeError):
    """A worker exhausted its restart budget; carries the failure history."""

    def __init__(self, msg, history=None):
        super().__init__(msg)
        self.history = history or []


class WorkerSpec:
    """One supervised worker: a subprocess command or a picklable callable.

    cmd       : list[str] argv (subprocess) OR a callable (multiprocessing
                spawn; must be importable from the child).
    args      : positional args for a callable target.
    env       : extra env vars layered over os.environ (+ the elastic
                contract vars the supervisor adds per incarnation).
    log_path  : file receiving stdout+stderr (subprocess targets only);
                appended across restarts so incarnations stay visible.
    """

    def __init__(self, cmd, args=(), env=None, log_path=None):
        self.cmd = cmd
        self.args = tuple(args)
        self.env = dict(env or {})
        self.log_path = log_path


class _Handle:
    """Supervisor-side state for one worker rank."""

    def __init__(self, rank, spec, heartbeat_path):
        self.rank = rank
        self.spec = spec
        self.heartbeat_path = heartbeat_path
        self.proc = None            # Popen or mp.Process
        self.restarts = 0           # completed restarts (incarnation - 1)
        self.done = False
        self.restart_at = None      # monotonic deadline while backing off
        self.started_at = None
        self.history = []           # [(incarnation, reason)]

    def alive(self):
        if self.proc is None:
            return False
        if hasattr(self.proc, "poll"):
            return self.proc.poll() is None
        return self.proc.is_alive()

    def exitcode(self):
        if hasattr(self.proc, "poll"):
            return self.proc.poll()
        return self.proc.exitcode

    def kill(self):
        if self.proc is None:
            return
        try:
            if hasattr(self.proc, "poll"):
                self.proc.kill()
            else:
                self.proc.terminate()
                if self.proc.is_alive():
                    self.proc.kill()
        except (OSError, AttributeError, ValueError):
            pass


def _mp_worker(func, rank, nprocs, args, env):
    os.environ.update(env)
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    func(*args)


class ElasticSupervisor:
    """Supervise a gang of workers: restart crashes, detect hangs.

    Policy knobs:
      max_restarts       per-worker restart budget (exceeding it fails the
                         whole job, reference elastic's scale-in semantics
                         reduced to fail-fast on a single host)
      backoff_base/factor/max
                         capped exponential backoff between restarts of the
                         SAME rank: delay = min(max, base * factor**n)
      jitter             multiplicative jitter fraction in [0, jitter)
                         added to each delay so a correlated crash of many
                         ranks doesn't produce a synchronized restart storm
      heartbeat_timeout  seconds without a beat before a worker counts as
                         hung (None disables hang detection)
      monitor_interval   supervisor poll period
    """

    def __init__(self, max_restarts: int = 3, backoff_base: float = 0.25,
                 backoff_factor: float = 2.0, backoff_max: float = 30.0,
                 jitter: float = 0.25,
                 heartbeat_timeout: Optional[float] = None,
                 monitor_interval: float = 0.05,
                 heartbeat_dir: Optional[str] = None,
                 seed: Optional[int] = None):
        self.max_restarts = int(max_restarts)
        self.backoff_base = float(backoff_base)
        self.backoff_factor = float(backoff_factor)
        self.backoff_max = float(backoff_max)
        self.jitter = float(jitter)
        self.heartbeat_timeout = heartbeat_timeout
        self.monitor_interval = float(monitor_interval)
        self.heartbeat_dir = heartbeat_dir
        self._backoff = BackoffPolicy(base=backoff_base,
                                      factor=backoff_factor,
                                      max_delay=backoff_max,
                                      jitter=jitter, seed=seed)

    # ------------------------------------------------------------- backoff
    def backoff_delay(self, n_prev_restarts: int) -> float:
        """Delay before restart #(n_prev_restarts+1) of one rank."""
        return self._backoff.delay(n_prev_restarts)

    # -------------------------------------------------------------- launch
    def _start(self, h: _Handle, nprocs: int):
        from .. import obs
        with obs.span("elastic.start", cat="restart", annotate=False,
                      args={"rank": h.rank, "incarnation": h.restarts}):
            self._start_inner(h, nprocs)

    def _start_inner(self, h: _Handle, nprocs: int):
        spec = h.spec
        env = dict(os.environ)
        # spec.env may override the default rank mapping (multi-node
        # launch passes globally-numbered PADDLE_TRAINER_ID); the
        # supervisor-owned elastic vars are applied last and always win
        env.update({"PADDLE_TRAINER_ID": str(h.rank),
                    "PADDLE_TRAINERS_NUM": str(nprocs)})
        env.update(spec.env)
        env.update({
            RESTART_COUNT_ENV: str(h.restarts),
            MAX_RESTARTS_ENV: str(self.max_restarts),
            HEARTBEAT_FILE_ENV: h.heartbeat_path,
        })
        # fresh heartbeat baseline per incarnation: a stale beat from the
        # previous (killed) incarnation must not instantly re-trip the
        # hang detector
        try:
            os.remove(h.heartbeat_path)
        except OSError:
            pass
        if callable(spec.cmd):
            import multiprocessing as mp
            ctx = mp.get_context("spawn")
            child_env = {k: env[k] for k in
                         (RESTART_COUNT_ENV, MAX_RESTARTS_ENV,
                          HEARTBEAT_FILE_ENV)}
            child_env.update(spec.env)
            h.proc = ctx.Process(
                target=_mp_worker,
                args=(spec.cmd, h.rank, nprocs, spec.args, child_env))
            h.proc.start()
        else:
            out = open(spec.log_path, "a") if spec.log_path else None
            h.proc = subprocess.Popen(
                list(spec.cmd), env=env, stdout=out,
                stderr=subprocess.STDOUT if out else None)
            if out is not None:
                out.close()  # child holds its own fd
        h.started_at = time.monotonic()
        h.restart_at = None

    def _hung(self, h: _Handle) -> bool:
        if self.heartbeat_timeout is None:
            return False
        try:
            mtime = os.path.getmtime(h.heartbeat_path)
        except OSError:
            return False  # no beat yet: still starting up (compile/import)
        return (time.time() - mtime) > self.heartbeat_timeout

    def _fail(self, h: _Handle, reason: str, handles: List[_Handle]):
        h.history.append((h.restarts, reason))
        if h.restarts >= self.max_restarts:
            for other in handles:
                other.kill()
            raise ElasticJobError(
                f"worker rank {h.rank} failed ({reason}) and exhausted its "
                f"restart budget ({self.max_restarts}); history: "
                f"{h.history}", history=h.history)
        delay = self.backoff_delay(h.restarts)
        h.restarts += 1
        h.proc = None
        h.restart_at = time.monotonic() + delay
        # obs telemetry: restart decisions, labeled hang vs crash (the
        # free-form reason string is too high-cardinality for a label)
        from .. import obs
        kind = "hang" if reason.startswith("hang") else "crash"
        obs.counter("elastic_restarts_total",
                    "worker restarts scheduled by the elastic supervisor",
                    labels=("kind",)).labels(kind=kind).inc()

    # ----------------------------------------------------------------- run
    def run(self, workers: Union[Callable, Sequence], args=(), nprocs=None):
        """Run the gang to completion; returns a per-rank report.

        `workers` is a list of WorkerSpec / argv lists, OR a single callable
        (with `args`/`nprocs`, spawn-style). Raises ElasticJobError once any
        rank exceeds max_restarts.
        """
        if callable(workers):
            specs = [WorkerSpec(workers, args=args)
                     for _ in range(nprocs or 1)]
        else:
            specs = [w if isinstance(w, WorkerSpec) else WorkerSpec(list(w))
                     for w in workers]
        n = len(specs)
        hb_dir = self.heartbeat_dir
        if hb_dir is None:
            import tempfile
            hb_dir = tempfile.mkdtemp(prefix="paddle_elastic_hb_")
        os.makedirs(hb_dir, exist_ok=True)
        handles = [_Handle(r, s, os.path.join(hb_dir, f"hb.{r}"))
                   for r, s in enumerate(specs)]
        for h in handles:
            self._start(h, n)
        try:
            while not all(h.done for h in handles):
                for h in handles:
                    if h.done:
                        continue
                    if h.proc is None:  # backing off
                        if time.monotonic() >= h.restart_at:
                            self._start(h, n)
                        continue
                    if h.alive():
                        if self._hung(h):
                            h.kill()
                            # reap before restarting so the dead incarnation
                            # can't be polled as a crash next iteration
                            self._join(h)
                            self._fail(h, "hang (heartbeat timeout)",
                                       handles)
                        continue
                    code = self.exit_of(h)
                    if code == 0:
                        h.done = True
                    else:
                        self._fail(h, f"exit code {code}", handles)
                time.sleep(self.monitor_interval)
        except BaseException:
            for h in handles:
                h.kill()
            raise
        return {
            "nprocs": n,
            "restarts": {h.rank: h.restarts for h in handles},
            "history": {h.rank: list(h.history) for h in handles},
        }

    @staticmethod
    def _join(h: _Handle):
        try:
            if hasattr(h.proc, "wait"):
                h.proc.wait(timeout=10)
            else:
                h.proc.join(timeout=10)
        except Exception:
            pass

    @staticmethod
    def exit_of(h: _Handle) -> int:
        code = h.exitcode()
        return 1 if code is None else code


def elastic_spawn(func, args=(), nprocs=1, max_restarts=3,
                  heartbeat_timeout=None, **options):
    """`paddle.distributed.spawn` with supervision: crashed workers restart
    with backoff and resume from the last auto-checkpoint instead of
    failing the job (drop-in for spawn(join=True))."""
    sup = ElasticSupervisor(max_restarts=max_restarts,
                            heartbeat_timeout=heartbeat_timeout, **options)
    return sup.run(func, args=args, nprocs=nprocs)


def main(argv=None):
    """python -m paddle_tpu.distributed.elastic [--flags] script args...

    The command-line face of the supervisor, mirroring
    `paddle_tpu.distributed.launch` but fault-tolerant: each of
    --nproc_per_node workers is restarted on crash/hang up to
    --max_restarts times.
    """
    import argparse
    ap = argparse.ArgumentParser("paddle_tpu.distributed.elastic")
    ap.add_argument("--nproc_per_node", type=int, default=1)
    ap.add_argument("--max_restarts", type=int, default=3)
    ap.add_argument("--heartbeat_timeout", type=float, default=None)
    ap.add_argument("--backoff_base", type=float, default=0.25)
    ap.add_argument("--backoff_max", type=float, default=30.0)
    ap.add_argument("--log_dir", default=None)
    ap.add_argument("training_script")
    ap.add_argument("training_script_args", nargs=argparse.REMAINDER)
    ns = ap.parse_args(argv)
    if ns.log_dir:
        os.makedirs(ns.log_dir, exist_ok=True)
    specs = []
    for rank in range(ns.nproc_per_node):
        log = (os.path.join(ns.log_dir, f"worker.{rank}.log")
               if ns.log_dir else None)
        specs.append(WorkerSpec(
            [sys.executable, ns.training_script] + ns.training_script_args,
            env={"FLAGS_selected_tpus": str(rank)}, log_path=log))
    sup = ElasticSupervisor(max_restarts=ns.max_restarts,
                            heartbeat_timeout=ns.heartbeat_timeout,
                            backoff_base=ns.backoff_base,
                            backoff_max=ns.backoff_max)
    report = sup.run(specs)
    print(f"elastic job done: restarts={report['restarts']}")


if __name__ == "__main__":
    main()
