"""paddle.distributed.spawn parity.

Reference: ``python/paddle/distributed/spawn.py`` — fork N worker processes,
set per-rank PADDLE_* env, run ``func`` in each, join and re-raise failures.

TPU-native shape: a real TPU pod is driven one-process-per-HOST via
``paddle_tpu.distributed.launch`` (single-controller per host), so spawn's
job here is the single-host multi-process development path: N CPU-backend
``jax.distributed`` processes on one machine — the same world the reference
builds with one GPU per process. Each child gets PADDLE_TRAINER_ID /
PADDLE_TRAINERS_NUM / PADDLE_TPU_COORDINATOR so ``init_parallel_env()``
inside ``func`` forms the collective world.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import socket
import sys


def _free_port() -> int:
    """Probe for a free port. TOCTOU by construction: the socket closes
    before the child coordinator binds, so on busy hosts another process can
    grab the port in between — ``spawn`` retries the whole launch with a
    fresh port when a worker dies on a bind failure (exit ``_BIND_EXIT``)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# Distinctive exit code for "coordinator port was taken" (EADDRINUSE=98):
# the parent's join maps it to a retry-with-fresh-port instead of a failure.
_BIND_EXIT = 98


def _is_bind_error(e: BaseException) -> bool:
    s = str(e).lower()
    return ("address already in use" in s or "eaddrinuse" in s
            or "failed to bind" in s or "errno 98" in s
            or "error binding" in s)


def _worker(func, args):
    # env is inherited from the parent's per-rank os.environ snapshot (set
    # around p.start()): it must be in place BEFORE this function body runs,
    # because unpickling the target itself imports paddle_tpu (and jax).
    try:
        func(*args)
    except Exception as e:
        if _is_bind_error(e):
            sys.stderr.write(
                f"paddle_tpu.distributed.spawn worker: coordinator bind "
                f"failed ({e}); exiting {_BIND_EXIT} for port retry\n"
            )
            sys.exit(_BIND_EXIT)
        raise


def _default_join_timeout():
    """Default SpawnContext.join deadline in seconds (env-overridable via
    PADDLE_TPU_SPAWN_JOIN_TIMEOUT_S; 0 or unset-able to ``none`` disables).
    A wedged child must surface as a reaped, reported failure — never as a
    parent blocked forever."""
    raw = os.environ.get("PADDLE_TPU_SPAWN_JOIN_TIMEOUT_S", "")
    if not raw:
        return 3600.0
    try:
        t = float(raw)
    except ValueError:
        return 3600.0
    return t if t > 0 else None


def _last_progress(ranks, pdir=None):
    """Each rank's last watchdog progress record, read from the launch's
    PADDLE_TPU_PROGRESS_DIR (set by _launch for its children). The wedged-
    child report: WHERE each rank was when the parent gave up on it."""
    pdir = pdir or os.environ.get("PADDLE_TPU_PROGRESS_DIR")
    if not pdir:
        return {}
    try:
        from .watchdog import _read_progress_dir

        table = _read_progress_dir(pdir)
    except Exception:
        return {}
    return {r: table[r] for r in ranks if r in table}


class SpawnContext:
    def __init__(self, procs, progress_dir=None):
        self.processes = procs
        self.progress_dir = progress_dir
        # ranks that exited with the preemption drain's RESUMABLE_EXIT_CODE
        # (75) in the last join(): the world checkpointed cleanly and asked
        # for a restart — spawn() honors it the way launch_mod does
        self.resumable_ranks = []

    def join(self, timeout="default"):
        """Wait for all workers, POLLING so one crashed rank is detected even
        while its peers sit blocked in a collective waiting for it — the rest
        are then terminated and the failure raised (the reference's
        watch-and-kill loop in spawn.py).

        ``timeout="default"`` applies the env-overridable deadline
        (PADDLE_TPU_SPAWN_JOIN_TIMEOUT_S, 3600s unset): past it the parent
        REAPS the remaining children and raises a report carrying each
        wedged rank's last progress record instead of blocking forever.
        ``timeout=None`` waits indefinitely; a number is an explicit
        deadline past which join returns False (legacy polling contract).

        Exit code 75 (RESUMABLE_EXIT_CODE) is NOT a failure: those ranks are
        recorded in ``resumable_ranks`` and join returns True — the caller
        (``spawn``) relaunches the world, same as launch_mod."""
        import time

        from ..fault.preemption import RESUMABLE_EXIT_CODE

        reap_on_deadline = timeout == "default"
        if reap_on_deadline:
            timeout = _default_join_timeout()
        deadline = None if timeout is None else time.monotonic() + timeout
        self.resumable_ranks = []
        while True:
            bad = [(r, p.exitcode) for r, p in enumerate(self.processes)
                   if p.exitcode not in (0, RESUMABLE_EXIT_CODE, None)]
            if bad:
                for p in self.processes:  # one failure sinks the job
                    if p.is_alive():
                        p.terminate()
                for p in self.processes:
                    p.join(5)
                rank, code = bad[0]
                err = RuntimeError(
                    f"spawn worker rank {rank} exited with code {code} "
                    f"({len(bad)} of {len(self.processes)} workers failed)"
                )
                # a _BIND_EXIT rank means the probed coordinator port was
                # taken before the child bound it (TOCTOU) — spawn() retries
                err.bind_failure = any(c == _BIND_EXIT for _, c in bad)
                raise err
            alive = [p for p in self.processes if p.exitcode is None]
            if not alive:
                self.resumable_ranks = [
                    r for r, p in enumerate(self.processes)
                    if p.exitcode == RESUMABLE_EXIT_CODE
                ]
                return True
            if deadline is not None and time.monotonic() >= deadline:
                if not reap_on_deadline:
                    return False
                wedged = [r for r, p in enumerate(self.processes)
                          if p.exitcode is None]
                progress = _last_progress(wedged, self.progress_dir)
                for p in self.processes:
                    if p.is_alive():
                        p.terminate()
                for p in self.processes:
                    p.join(5)
                detail = "; ".join(
                    f"rank {r}: last progress "
                    + (f"step {progress[r].get('step')} phase "
                       f"{progress[r].get('phase')!r}" if r in progress
                       else "never published")
                    for r in wedged
                )
                raise RuntimeError(
                    f"spawn: workers {wedged} still running after "
                    f"{timeout:.0f}s join deadline — reaped ({detail}). "
                    "Raise PADDLE_TPU_SPAWN_JOIN_TIMEOUT_S for longer jobs."
                )
            alive[0].join(0.2)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, backend=None,
          **options):
    """Run ``func(*args)`` in ``nprocs`` processes forming one collective
    world. ``nprocs<=1`` runs inline (single-controller fast path). Children
    default to the CPU backend: one host has one TPU client, and N processes
    contending for it is never what a multi-process dev run means — multihost
    TPU launches go through ``paddle_tpu.distributed.launch`` instead."""
    if nprocs in (-1, 0):
        nprocs = 1
    if nprocs <= 1:
        func(*args)
        return None if join else SpawnContext([])
    if backend is None:
        backend = "cpu"
    bind_retries = max(int(options.pop("bind_retries", 3)), 1)
    max_resumes = max(int(options.pop("max_resumes", 32)), 0)
    bind_attempt = 0
    resumes = 0
    while True:
        context = _launch(func, args, nprocs, backend, daemon, options)
        if not join:
            # caller owns the join — no bind-retry possible past this point
            return context
        try:
            context.join()
        except RuntimeError as e:
            if not getattr(e, "bind_failure", False) \
                    or bind_attempt >= bind_retries - 1:
                raise
            # coordinator port raced away (classic TOCTOU on busy hosts):
            # relaunch the whole world on a fresh probe port
            bind_attempt += 1
            continue
        if not context.resumable_ranks:
            return None
        # RESUMABLE_EXIT_CODE (75): the world drained + checkpointed and
        # wants a restart — honor it exactly like launch_mod, on a separate
        # (larger) budget than real failures
        resumes += 1
        if resumes > max_resumes:
            raise RuntimeError(
                f"spawn: workers asked for more than max_resumes="
                f"{max_resumes} restarts (ranks {context.resumable_ranks} "
                "exited resumably again)"
            )


def _launch(func, args, nprocs, backend, daemon, options):
    import tempfile

    coordinator = f"127.0.0.1:{_free_port()}"
    ctx = mp.get_context("spawn")
    procs = []
    # distributed-supervision substrate for the children: a shared progress
    # dir (watchdog publications — the parent's wedged-child report reads
    # it) and a FileStore dir (coordinated checkpoint commit barrier).
    # An env-provided dir (chaos harness, nested launches) wins.
    progress_dir = os.environ.get("PADDLE_TPU_PROGRESS_DIR") or tempfile.mkdtemp(
        prefix="paddle_tpu_progress_"
    )
    store_dir = os.environ.get("PADDLE_TPU_STORE_DIR") or tempfile.mkdtemp(
        prefix="paddle_tpu_store_"
    )
    # Children must see the worker env BEFORE their first import: unpickling
    # the process target imports paddle_tpu (and thus jax), so env set inside
    # the child function body is too late. Mutate os.environ around each
    # p.start() (children snapshot it at exec) and restore after.
    child_env = {
        "PADDLE_TRAINERS_NUM": str(nprocs),
        "PADDLE_TPU_COORDINATOR": coordinator,
        "JAX_PLATFORMS": backend,
        "PADDLE_TPU_PROGRESS_DIR": progress_dir,
        "PADDLE_TPU_STORE_DIR": store_dir,
    }
    child_env.update(options.get("env", {}))
    saved = {k: os.environ.get(k) for k in (*child_env, "PADDLE_TRAINER_ID",
                                            "PADDLE_LOCAL_RANK")}
    try:
        os.environ.update(child_env)
        for rank in range(nprocs):
            os.environ["PADDLE_TRAINER_ID"] = str(rank)
            os.environ["PADDLE_LOCAL_RANK"] = str(rank)
            p = ctx.Process(target=_worker, args=(func, args), daemon=daemon)
            p.start()
            procs.append(p)
    except BaseException:
        # a failed start() mid-loop must not orphan earlier ranks — they sit
        # blocked in the jax.distributed rendezvous for a world that will
        # never form
        for p in procs:
            if p.is_alive():
                p.terminate()
        for p in procs:
            p.join(5)
        raise
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return SpawnContext(procs, progress_dir=progress_dir)
