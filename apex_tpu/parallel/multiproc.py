"""Multi-process launcher — parity shim for ``python -m apex.parallel.multiproc``.

ref: apex/parallel/multiproc.py:12-35 (spawn world_size copies of the script
with ``--rank i`` appended and wait).

On TPU pods the runtime launches one process per host and
``jax.distributed.initialize()`` wires the cluster, so the launcher's real
job disappears.  This module keeps three useful pieces:

- :func:`init_distributed` — env-driven jax.distributed bootstrap (the
  moral twin of ``init_process_group('nccl', 'env://')``), with the
  coordinator-init timeout configurable via
  ``APEX_TPU_DIST_INIT_TIMEOUT_S``;
- :func:`launch` — the programmatic gang spawn the fleet train
  launcher (:mod:`apex_tpu.fleet.train`) builds on: N local processes
  with coordinator env vars set, each worker's stderr captured so a
  failed or timed-out gang SURFACES the failing rank's stderr tail in
  the raised :class:`MultiprocError` instead of swallowing it (the
  pre-ISSUE-9 failure mode: a coordinator-init timeout died with no
  diagnostics);
- ``python -m apex_tpu.parallel.multiproc script.py ...`` — the CLI
  over :func:`launch`, for exercising the multi-process (DCN) code
  path without hardware.

This is the HARDWARE-FREE rehearsal of the multi-host path, not a chip
launcher: :func:`launch` defaults every worker to ``JAX_PLATFORMS=cpu``.
A chip belongs to one process at a time — N local workers cannot share
one host's chips — so on a TPU host ONE process drives all of its chips
(a mesh over ``jax.devices()``), and on a pod the runtime starts that
one process per host.
"""
from __future__ import annotations

import dataclasses
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence

__all__ = [
    "MultiprocError",
    "TEARDOWN_RC",
    "WorkerResult",
    "dist_init_timeout_s",
    "init_distributed",
    "launch",
    "main",
]

DEFAULT_STDERR_TAIL = 2000  # bytes of worker stderr quoted in errors

#: the exit code of a worker the LAUNCHER killed during gang teardown
#: (``p.kill()`` = SIGKILL) — an innocent bystander of a peer's death,
#: never a rank that failed on its own (elastic gangs must not charge
#: teardown victims against their restart budget)
TEARDOWN_RC = -int(signal.SIGKILL)


def dist_init_timeout_s(timeout: Optional[int] = None) -> int:
    """Coordinator-init timeout in seconds (explicit arg >
    ``APEX_TPU_DIST_INIT_TIMEOUT_S`` env > jax's default 300).  Local
    CPU gangs want this SHORT: a worker that dies before
    ``jax.distributed.initialize`` leaves its peers blocked on the
    coordinator for the full timeout."""
    if timeout is not None:
        return int(timeout)
    return int(os.environ.get("APEX_TPU_DIST_INIT_TIMEOUT_S", "300"))


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    initialization_timeout: int | None = None,
) -> None:
    """Initialize jax.distributed from args or env.

    Env parity with torch.distributed.launch: MASTER_ADDR/MASTER_PORT,
    WORLD_SIZE, RANK (ref examples/simple/distributed/
    distributed_data_parallel.py:15-28) — also accepts the JAX-native
    COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID.  The coordinator-init
    timeout resolves via :func:`dist_init_timeout_s`.
    """
    import jax

    coord = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if coord is None and "MASTER_ADDR" in os.environ:
        coord = f"{os.environ['MASTER_ADDR']}:{os.environ.get('MASTER_PORT', '12355')}"
    nproc = num_processes or int(
        os.environ.get("NUM_PROCESSES", os.environ.get("WORLD_SIZE", "0"))
    )
    pid = process_id if process_id is not None else int(
        os.environ.get("PROCESS_ID", os.environ.get("RANK", "0"))
    )
    if coord and nproc:
        jax.distributed.initialize(
            coordinator_address=coord, num_processes=nproc, process_id=pid,
            initialization_timeout=dist_init_timeout_s(
                initialization_timeout
            ),
        )


class MultiprocError(RuntimeError):
    """A gang failed or timed out; the message carries every failing
    rank's stderr tail (the diagnosable version of "exit code 1")."""

    def __init__(self, message: str, results: List["WorkerResult"]):
        super().__init__(message)
        self.results = results

    def guilty_ranks(self) -> List[int]:
        """Ranks that died of their OWN exit — nonzero and not the
        teardown SIGKILL the launcher deals the rest of the gang.  The
        elastic gang launcher charges exactly these against per-rank
        restart budgets; a timed-out gang (everyone torn down) has no
        guilty rank and relaunches at the same world."""
        return [r.rank for r in self.results
                if r.returncode not in (0, None, TEARDOWN_RC)]


@dataclasses.dataclass
class WorkerResult:
    """One gang member's outcome: exit code (None = killed on gang
    teardown before exiting), its captured stderr tail, and its wall
    time from spawn to reap (``wall_s``; a teardown victim's wall runs
    to the teardown, so per-rank walls are comparable — the
    launcher-side annotation gang telemetry reports alongside the
    workers' own K-boundary rows)."""

    rank: int
    returncode: Optional[int]
    stderr_tail: str = ""
    wall_s: Optional[float] = None

    @property
    def ok(self) -> bool:
        return self.returncode == 0


def _tail(path: str, nbytes: int = DEFAULT_STDERR_TAIL) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - nbytes))
            return f.read().decode("utf-8", "replace")
    except OSError:
        return ""


def launch(
    argv: Sequence[str],
    world_size: int = 2,
    *,
    env: Optional[Dict[str, str]] = None,
    timeout_s: Optional[float] = None,
    master_port: Optional[int] = None,
    echo_stderr: bool = True,
    check: bool = False,
) -> List[WorkerResult]:
    """Spawn ``world_size`` copies of ``argv`` as one gang.

    Each worker gets MASTER_ADDR/MASTER_PORT/WORLD_SIZE/RANK (the
    torch.distributed.launch env parity ``init_distributed`` consumes)
    and its stderr captured to a temp file.  The gang is reaped as a
    UNIT: the first nonzero exit (or ``timeout_s`` expiring — e.g. the
    surviving ranks blocked in a coordinator-init timeout after a peer
    died) kills the rest.  Returns per-rank :class:`WorkerResult`\\ s;
    with ``check=True`` a failed/timed-out gang raises
    :class:`MultiprocError` quoting the failing ranks' stderr tails.
    ``echo_stderr`` replays every worker's stderr to this process's
    stderr on completion (so interactive runs still see worker
    tracebacks).
    """
    argv = list(argv)
    base_env = dict(os.environ if env is None else env)
    procs: List[subprocess.Popen] = []
    logs: List[str] = []
    spawned: List[float] = []
    reaped: Dict[int, float] = {}
    try:
        for rank in range(world_size):
            wenv = dict(base_env)
            wenv.update(
                MASTER_ADDR="127.0.0.1",
                MASTER_PORT=str(
                    master_port
                    if master_port is not None
                    else wenv.get("MASTER_PORT", "12355")
                ),
                WORLD_SIZE=str(world_size),
                RANK=str(rank),
                # CPU unless the caller says otherwise: local workers
                # rehearse the multi-host path, they never share a
                # host's chips (one process per chip — module docstring)
                JAX_PLATFORMS=wenv.get("JAX_PLATFORMS", "cpu"),
            )
            # ref appends --rank i (multiproc.py:28-31); we export RANK
            fd, log = tempfile.mkstemp(prefix=f"apex_gang_r{rank}_",
                                       suffix=".stderr")
            logs.append(log)
            stderr = os.fdopen(fd, "wb")
            spawned.append(time.time())
            procs.append(subprocess.Popen(
                [sys.executable] + argv, env=wenv, stderr=stderr
            ))
            stderr.close()  # the child holds its own handle

        deadline = None if timeout_s is None else time.time() + timeout_s
        timed_out = False
        pending = set(range(world_size))
        failed = False
        while pending:
            progressed = False
            for rank in sorted(pending):
                rc = procs[rank].poll()
                if rc is not None:
                    pending.discard(rank)
                    reaped[rank] = time.time()
                    progressed = True
                    if rc != 0:
                        failed = True
            if failed:
                break  # reap the gang below: one death dooms the rest
            if deadline is not None and time.time() > deadline:
                timed_out = True
                break
            if pending and not progressed:
                time.sleep(0.05)
        for rank, p in enumerate(procs):  # gang teardown
            if p.poll() is None:
                p.kill()
                reaped.setdefault(rank, time.time())
        for p in procs:
            p.wait()
    finally:
        t_end = time.time()
        results = [
            WorkerResult(rank=r, returncode=procs[r].poll()
                         if r < len(procs) else None,
                         stderr_tail=_tail(logs[r])
                         if r < len(logs) else "",
                         wall_s=round(
                             reaped.get(r, t_end) - spawned[r], 3
                         ) if r < len(spawned) else None)
            for r in range(world_size)
        ]
        for log in logs:
            try:
                os.unlink(log)
            except OSError:
                pass
    if echo_stderr:
        for res in results:
            if res.stderr_tail:
                sys.stderr.write(res.stderr_tail)
        sys.stderr.flush()
    bad = [r for r in results if not r.ok]
    if check and (bad or timed_out):
        what = (f"gang timed out after {timeout_s}s"
                if timed_out else "gang failed")
        detail = "\n".join(
            f"--- rank {r.rank} (rc={r.returncode}) stderr tail ---\n"
            f"{r.stderr_tail.strip() or '(empty)'}"
            for r in bad or results
        )
        raise MultiprocError(
            f"{what} (world_size={world_size}, argv={argv!r}):\n{detail}",
            results,
        )
    return results


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    world_size = int(os.environ.get("WORLD_SIZE", "2"))
    if not argv:
        print("usage: python -m apex_tpu.parallel.multiproc script.py [args...]")
        return 2
    results = launch(argv, world_size)
    rc = 0
    for r in results:  # ref waits on children (multiproc.py:34-35)
        rc = (r.returncode or 0) or rc
    return rc


if __name__ == "__main__":
    sys.exit(main())
