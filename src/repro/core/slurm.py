"""Batch-executor interface with Slurm semantics + a local implementation.

The paper targets Slurm "as a synonym for all other HPC job schedulers" (§2.7)
and the presented extension is "a template for corresponding extensions for
other job schedulers". Accordingly the scheduler (:mod:`repro.core.scheduler`)
talks to this small interface; :class:`LocalSlurmCluster` implements it with a
thread pool + subprocesses so the complete protocol is executable and testable
in this container, reproducing:

  - sbatch/sacct/scancel semantics and job states
    (PENDING / RUNNING / COMPLETED / FAILED / CANCELLED / TIMEOUT),
  - array jobs (one submission, many tasks, per-task states; the array is
    COMPLETED only if every task is),
  - the ``log.slurm-<id>.out`` output file and the ``slurm-job-<id>.env.json``
    metadata file of paper §5.2,
  - submission latency on the shared virtual clock (``sbatch_cost_s`` ≈ the
    paper's measured ~0.05 s baseline) so benchmarks can compare
    schedule-vs-sbatch like Figure 7.

On a real cluster, a ``SubprocessSlurmCluster`` shelling out to the real
``sbatch``/``sacct`` is a drop-in replacement (provided, but not exercisable
here).
"""
from __future__ import annotations

import json
import os
import subprocess
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

from . import faults as _faults
from .fsio import SimClock

# canonical Slurm states we model
PENDING = "PENDING"
RUNNING = "RUNNING"
COMPLETED = "COMPLETED"
FAILED = "FAILED"
CANCELLED = "CANCELLED"
TIMEOUT = "TIMEOUT"
NODE_FAIL = "NODE_FAIL"
PREEMPTED = "PREEMPTED"
TERMINAL = {COMPLETED, FAILED, CANCELLED, TIMEOUT, NODE_FAIL, PREEMPTED}


def fold_states(states: list[str]) -> str:
    """Collapse raw per-task sacct state strings into one job state with the
    precedence both of SubprocessSlurmCluster's accounting paths (single and
    batched) share — a job is only COMPLETED when nothing else applies to
    any of its rows. NOTE: LocalSlurmCluster's ``aggregate_state`` orders
    terminal states CANCELLED > TIMEOUT > ... > FAILED instead; for mixed-
    terminal array jobs the simulated and real backends can report different
    (but equally terminal) states."""
    if not states:
        return PENDING
    for precedence in (
        RUNNING, PENDING, NODE_FAIL, PREEMPTED, FAILED, CANCELLED, TIMEOUT
    ):
        if any(s.startswith(precedence) for s in states):
            return precedence
    return COMPLETED


@dataclass
class TaskState:
    state: str = PENDING
    exit_code: int | None = None
    start_time: float | None = None
    end_time: float | None = None


@dataclass
class SlurmJob:
    job_id: int
    script: str
    args: str
    workdir: str
    array_n: int = 1
    time_limit_s: float | None = None
    env: dict | None = None  # extra job environment (RunSpec.env)
    submit_time: float = field(default_factory=time.time)
    tasks: list[TaskState] = field(default_factory=list)
    cancelled: bool = False
    dependency: list[int] = field(default_factory=list)  # afterok parents
    held: bool = False  # scontrol hold: stay PENDING even with no deps
    started: bool = False  # tasks handed to the pool (at most once)

    def aggregate_state(self) -> str:
        states = [t.state for t in self.tasks]
        if any(s == RUNNING for s in states):
            return RUNNING
        if any(s == PENDING for s in states):
            return PENDING
        if all(s == COMPLETED for s in states):
            return COMPLETED
        if any(s == CANCELLED for s in states):
            return CANCELLED
        if any(s == TIMEOUT for s in states):
            return TIMEOUT
        if any(s == NODE_FAIL for s in states):
            return NODE_FAIL
        if any(s == PREEMPTED for s in states):
            return PREEMPTED
        return FAILED


class SlurmCluster:
    """Executor interface (sbatch/sacct/scancel)."""

    def sbatch(self, script: str, workdir: str, args: str = "", array_n: int = 1,
               time_limit_s: float | None = None, env: dict | None = None,
               dependency: list[int] | None = None) -> int:
        """Submit a job. ``dependency`` is a list of parent job ids with
        ``afterok`` semantics: the job stays PENDING until every parent is
        COMPLETED, and is cancelled if any parent ends in another terminal
        state (real Slurm leaves it DependencyNeverSatisfied; we model the
        ``--kill-on-invalid-dep=yes`` behaviour so campaigns drain)."""
        raise NotImplementedError

    def scontrol_update_dependency(
        self, job_id: int, add: list[int] | None = None,
        remove: list[int] | None = None, hold: bool = False,
    ) -> bool:
        """Rewire a *pending* job's afterok parents (``scontrol update
        Dependency=...``). ``hold`` additionally holds the job so it does
        not start even if its dependency set becomes empty — callers use
        remove+hold, then add+release once the replacement parent exists.
        Returns False if the job already started or finished."""
        raise NotImplementedError

    def scontrol_release(self, job_id: int) -> None:
        """Clear a hold set by :meth:`scontrol_update_dependency`."""
        raise NotImplementedError

    def sacct(self, job_id: int) -> str:
        raise NotImplementedError

    def sacct_many(self, job_ids: list[int]) -> dict[int, str]:
        """States for a whole set of jobs in ONE accounting query (one CLI
        startup, not one per job). Backends override with a genuinely
        batched call; this fallback preserves semantics for exotic
        implementations that only provide ``sacct``."""
        return {j: self.sacct(j) for j in job_ids}

    def sacct_tasks(self, job_id: int) -> list[str]:
        raise NotImplementedError

    def scancel(self, job_id: int) -> str | None:
        """Cancel a job. Idempotent: cancelling an already-terminal or
        unknown job is a no-op. Returns the job's state after the call when
        the backend knows it (None for backends that don't report one)."""
        raise NotImplementedError

    def wait(self, job_ids: list[int] | None = None, timeout: float = 300.0) -> None:
        raise NotImplementedError


class LocalSlurmCluster(SlurmCluster):
    """Runs each job as a subprocess, up to ``max_workers`` at once.

    A TPU chip belongs to one process at a time, and a process that has
    touched JAX holds it. A campaign whose jobs use the chip therefore needs
    a parent that never imports JAX and ``max_workers=1`` (one chip job at
    a time); otherwise a job fails or hangs waiting for the chip."""

    def __init__(
        self,
        max_workers: int = 8,
        clock: SimClock | None = None,
        sbatch_cost_s: float = 0.05,
        sacct_cost_s: float = 0.02,
        first_job_id: int = 11_452_000,
        faults: "_faults.FaultPlan | None" = None,
    ):
        self.pool = ThreadPoolExecutor(max_workers=max_workers)
        self.clock = clock or SimClock()
        self.faults = faults
        self.sbatch_cost_s = sbatch_cost_s
        self.sacct_cost_s = sacct_cost_s
        self._jobs: dict[int, SlurmJob] = {}
        self._procs: dict[tuple[int, int], subprocess.Popen] = {}
        # RLock: dependency resolution runs inside _maybe_done, which is
        # reached both with and without the lock held
        self._lock = threading.RLock()
        self._next_id = first_job_id
        self._done_events: dict[int, threading.Event] = {}
        self._waiting: dict[int, set[int]] = {}  # held job -> unmet parents
        self._dependents: dict[int, list[int]] = {}  # parent -> held children

    # -- submission ------------------------------------------------------
    def sbatch(self, script: str, workdir: str, args: str = "", array_n: int = 1,
               time_limit_s: float | None = None, env: dict | None = None,
               dependency: list[int] | None = None) -> int:
        if self.faults is not None:
            self.faults.on_slurm("sbatch")
        self.clock.charge(self.sbatch_cost_s)
        if not os.path.exists(os.path.join(workdir, script)) and not os.path.isabs(script):
            raise FileNotFoundError(f"job script not found: {script} (cwd {workdir})")
        failed_parent = False
        with self._lock:
            # validate the whole dependency list BEFORE registering the job:
            # raising mid-registration would leave a phantom never-terminal
            # PENDING row plus stale _dependents entries for earlier parents
            for p in dependency or []:
                if p not in self._jobs:
                    raise KeyError(f"unknown dependency job {p}")
            job_id = self._next_id
            self._next_id += 1
            job = SlurmJob(
                job_id=job_id, script=script, args=args, workdir=workdir,
                array_n=array_n, time_limit_s=time_limit_s, env=env,
                tasks=[TaskState() for _ in range(array_n)],
                dependency=list(dependency or []),
            )
            self._jobs[job_id] = job
            self._done_events[job_id] = threading.Event()
            waiting: set[int] = set()
            for p in job.dependency:
                parent = self._jobs[p]
                # done-event set means the parent's dependent resolution
                # already ran (or is running): resolve this edge inline —
                # a late registration would never be visited again
                if self._done_events[p].is_set():
                    if parent.aggregate_state() != COMPLETED:
                        failed_parent = True
                    continue
                waiting.add(p)
                self._dependents.setdefault(p, []).append(job_id)
            if failed_parent:
                self._detach(job_id)
            elif waiting:
                self._waiting[job_id] = waiting
        if failed_parent:
            self._cancel_dependent(job)
        elif not waiting:
            self._start_tasks(job)
        return job_id

    def _start_tasks(self, job: SlurmJob) -> None:
        with self._lock:
            if job.started or job.cancelled:
                return
            job.started = True
        for task_id in range(job.array_n):
            self.pool.submit(self._run_task, job, task_id)

    def _detach(self, job_id: int) -> None:
        """Drop every parent->job_id registration (lock held by caller)."""
        self._waiting.pop(job_id, None)
        for deps in self._dependents.values():
            while job_id in deps:
                deps.remove(job_id)

    def _cancel_dependent(self, job: SlurmJob) -> None:
        """A parent ended non-COMPLETED: the afterok child dies PENDING."""
        with self._lock:
            job.cancelled = True
            for t in job.tasks:
                if t.state == PENDING:
                    t.state = CANCELLED
        self._maybe_done(job)

    def _resolve_dependents(self, job: SlurmJob) -> None:
        """Called once `job` is terminal: release or cancel held children."""
        state = job.aggregate_state()
        to_start: list[SlurmJob] = []
        to_cancel: list[SlurmJob] = []
        with self._lock:
            for child_id in self._dependents.pop(job.job_id, []):
                waiting = self._waiting.get(child_id)
                if waiting is None:
                    continue
                child = self._jobs[child_id]
                if state == COMPLETED:
                    waiting.discard(job.job_id)
                    if not waiting:
                        del self._waiting[child_id]
                        if not child.held:
                            to_start.append(child)
                else:
                    self._detach(child_id)
                    to_cancel.append(child)
        for child in to_start:
            self._start_tasks(child)
        for child in to_cancel:
            self._cancel_dependent(child)  # cascades via _maybe_done

    def _log_path(self, job: SlurmJob, task_id: int) -> str:
        if job.array_n > 1:
            return os.path.join(job.workdir, f"log.slurm-{job.job_id}_{task_id}.out")
        return os.path.join(job.workdir, f"log.slurm-{job.job_id}.out")

    def _run_task(self, job: SlurmJob, task_id: int) -> None:
        task = job.tasks[task_id]
        with self._lock:
            if job.cancelled:
                task.state = CANCELLED
                self._maybe_done(job)
                return
            task.state = RUNNING
            task.start_time = time.time()
        if self.faults is not None:
            # injected node-level fate (NODE_FAIL / PREEMPTED / TIMEOUT /
            # FAILED): the task "ran" on a node that died — it never gets
            # to execute, but accounting still reports a terminal state
            try:
                fate = self.faults.task_fate()
            except _faults.CrashInjected:
                fate = None  # the *client* died; compute nodes are unaffected
            if fate is not None:
                task.state = fate
                task.exit_code = -1
                task.end_time = time.time()
                self._write_env_json(job)
                self._maybe_done(job)
                return
        env = dict(os.environ)
        if job.env:
            env.update(job.env)  # spec env first; SLURM identity vars win
        env.update(
            SLURM_JOB_ID=str(job.job_id),
            SLURM_ARRAY_TASK_ID=str(task_id),
            SLURM_ARRAY_TASK_COUNT=str(job.array_n),
            SLURM_JOB_NAME=os.path.basename(job.script),
            SLURM_JOB_PARTITION="simulated",
            SLURM_JOB_NUM_NODES="1",
            SLURM_SUBMIT_DIR=job.workdir,
        )
        logpath = self._log_path(job, task_id)
        cmd = f"bash {job.script} {job.args}".strip()
        try:
            with open(logpath, "w") as log:
                proc = subprocess.Popen(
                    cmd, shell=True, cwd=job.workdir, env=env,
                    stdout=log, stderr=subprocess.STDOUT,
                )
                with self._lock:
                    self._procs[(job.job_id, task_id)] = proc
                try:
                    rc = proc.wait(timeout=job.time_limit_s)
                    task.exit_code = rc
                    task.state = COMPLETED if rc == 0 else FAILED
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
                    task.state = TIMEOUT
                    task.exit_code = -1
        except Exception:
            task.state = FAILED
            task.exit_code = -1
        finally:
            task.end_time = time.time()
            with self._lock:
                self._procs.pop((job.job_id, task_id), None)
                if job.cancelled and task.state not in (COMPLETED,):
                    task.state = CANCELLED
            self._write_env_json(job)
            self._maybe_done(job)

    def _write_env_json(self, job: SlurmJob) -> None:
        """The paper's extra output: slurm-job-<id>.env.json with all Slurm
        metadata about the job (§5.2)."""
        meta = {
            "SLURM_JOB_ID": job.job_id,
            "SLURM_JOB_NAME": os.path.basename(job.script),
            "SLURM_JOB_PARTITION": "simulated",
            "SLURM_SUBMIT_DIR": job.workdir,
            "SLURM_ARRAY_TASK_COUNT": job.array_n,
            "SubmitTime": job.submit_time,
            "State": job.aggregate_state(),
            "ExitCodes": [t.exit_code for t in job.tasks],
            "Elapsed": [
                (t.end_time - t.start_time) if t.start_time and t.end_time else None
                for t in job.tasks
            ],
        }
        path = os.path.join(job.workdir, f"slurm-job-{job.job_id}.env.json")
        with open(path, "w") as f:
            json.dump(meta, f, indent=1, sort_keys=True)

    def _maybe_done(self, job: SlurmJob) -> None:
        if all(t.state in TERMINAL for t in job.tasks):
            self._done_events[job.job_id].set()
            self._resolve_dependents(job)

    # -- queries -----------------------------------------------------------
    def sacct(self, job_id: int) -> str:
        if self.faults is not None:
            self.faults.on_slurm("sacct")
        self.clock.charge(self.sacct_cost_s)
        job = self._jobs.get(job_id)
        if job is None:
            raise KeyError(f"unknown slurm job {job_id}")
        return job.aggregate_state()

    def sacct_many(self, job_ids: list[int]) -> dict[int, str]:
        if not job_ids:
            return {}  # nothing to poll -> no CLI invocation, no charge
        if self.faults is not None:
            self.faults.on_slurm("sacct")
        # one poll = one CLI-startup charge, however many jobs it covers
        self.clock.charge(self.sacct_cost_s)
        out = {}
        for job_id in job_ids:
            job = self._jobs.get(job_id)
            if job is None:
                raise KeyError(f"unknown slurm job {job_id}")
            out[job_id] = job.aggregate_state()
        return out

    def sacct_tasks(self, job_id: int) -> list[str]:
        if self.faults is not None:
            self.faults.on_slurm("sacct")
        self.clock.charge(self.sacct_cost_s)
        return [t.state for t in self._jobs[job_id].tasks]

    def job_runtime(self, job_id: int) -> float | None:
        job = self._jobs[job_id]
        starts = [t.start_time for t in job.tasks if t.start_time]
        if not starts:
            return None
        ends = [t.end_time or time.time() for t in job.tasks]
        return max(ends) - min(starts)

    def slurm_output_files(self, job_id: int) -> list[str]:
        job = self._jobs[job_id]
        logs = [
            os.path.basename(self._log_path(job, t)) for t in range(job.array_n)
        ]
        return logs + [f"slurm-job-{job_id}.env.json"]

    # -- control -------------------------------------------------------------
    def scancel(self, job_id: int) -> str | None:
        """Idempotent cancel (real ``scancel`` semantics): unknown ids and
        already-terminal jobs are no-ops — a straggler that completed
        between being flagged and being cancelled keeps its COMPLETED state
        (the caller inspects the returned state to decide what to do)."""
        if self.faults is not None:
            self.faults.on_slurm("scancel")
        with self._lock:
            job = self._jobs.get(job_id)
            if job is None:
                return None
            if all(t.state in TERMINAL for t in job.tasks):
                return job.aggregate_state()
            job.cancelled = True
            self._detach(job_id)  # a directly-cancelled held job stops waiting
            for t in job.tasks:
                if t.state == PENDING:
                    t.state = CANCELLED
            procs = [
                p for (jid, _), p in self._procs.items() if jid == job_id
            ]
        for p in procs:
            p.kill()
        self._maybe_done(job)
        return job.aggregate_state()

    def scontrol_update_dependency(
        self, job_id: int, add: list[int] | None = None,
        remove: list[int] | None = None, hold: bool = False,
    ) -> bool:
        if self.faults is not None:
            self.faults.on_slurm("scontrol")
        failed_parent = False
        with self._lock:
            job = self._jobs.get(job_id)
            if job is None or job.started or job.cancelled:
                return False
            # validate before mutating: a KeyError mid-rewire would leave
            # the job half-detached and dropped from _waiting for good
            for a in add or []:
                if a not in self._jobs:
                    raise KeyError(f"unknown dependency job {a}")
            waiting = self._waiting.pop(job_id, set())
            for r in remove or []:
                waiting.discard(r)
                if r in self._dependents:
                    while job_id in self._dependents[r]:
                        self._dependents[r].remove(job_id)
                if r in job.dependency:
                    job.dependency.remove(r)
            for a in add or []:
                parent = self._jobs[a]
                job.dependency.append(a)
                if self._done_events[a].is_set():
                    if parent.aggregate_state() != COMPLETED:
                        failed_parent = True
                    continue
                waiting.add(a)
                self._dependents.setdefault(a, []).append(job_id)
            if hold:
                job.held = True
            if failed_parent:
                self._detach(job_id)
            elif waiting:
                self._waiting[job_id] = waiting
            release_now = not failed_parent and not waiting and not job.held
        if failed_parent:
            self._cancel_dependent(job)
        elif release_now:
            self._start_tasks(job)
        return True

    def scontrol_release(self, job_id: int) -> None:
        if self.faults is not None:
            self.faults.on_slurm("scontrol")
        with self._lock:
            job = self._jobs.get(job_id)
            if job is None:
                return
            job.held = False
            start = (
                not job.started and not job.cancelled
                and job_id not in self._waiting
            )
        if start:
            self._start_tasks(job)

    def wait(self, job_ids: list[int] | None = None, timeout: float = 300.0) -> None:
        ids = job_ids if job_ids is not None else list(self._jobs)
        deadline = time.time() + timeout
        for jid in ids:
            remaining = max(0.0, deadline - time.time())
            if not self._done_events[jid].wait(timeout=remaining):
                raise TimeoutError(f"slurm job {jid} did not finish in {timeout}s")

    def shutdown(self) -> None:
        self.pool.shutdown(wait=False, cancel_futures=True)


class SubprocessSlurmCluster(SlurmCluster):
    """Real-cluster backend: shells out to actual sbatch/sacct/scancel.

    Provided for deployment; cannot be exercised in this container (no Slurm).
    The command construction mirrors the datalad-slurm plugin.
    """

    def sbatch(self, script: str, workdir: str, args: str = "", array_n: int = 1,
               time_limit_s: float | None = None, env: dict | None = None,
               dependency: list[int] | None = None) -> int:
        cmd = ["sbatch", "--parsable"]
        if array_n > 1:
            cmd.append(f"--array=0-{array_n - 1}")
        if time_limit_s:
            cmd.append(f"--time={max(1, int(time_limit_s // 60))}")
        if dependency:
            # kill-on-invalid-dep so a failed parent drains the cone instead
            # of leaving DependencyNeverSatisfied jobs pinning the queue —
            # matching LocalSlurmCluster's cancel-on-parent-failure model
            cmd.append("--dependency=afterok:" + ":".join(str(d) for d in dependency))
            cmd.append("--kill-on-invalid-dep=yes")
        cmd += [script] + ([a for a in args.split() if a] if args else [])
        # spec env goes through the submission environment (sbatch defaults
        # to --export=ALL), not the --export flag — values with commas or
        # '=' would corrupt the flag's comma-separated list
        proc_env = {**os.environ, **env} if env else None
        out = subprocess.run(
            cmd, cwd=workdir, env=proc_env, capture_output=True, text=True,
            check=True,
        )
        return int(out.stdout.strip().split(";")[0])

    def sacct(self, job_id: int) -> str:
        out = subprocess.run(
            ["sacct", "-j", str(job_id), "-X", "-n", "-o", "State%20"],
            capture_output=True, text=True, check=True,
        )
        states = [s.strip().rstrip("+") for s in out.stdout.splitlines() if s.strip()]
        return fold_states(states)

    def sacct_many(self, job_ids: list[int]) -> dict[int, str]:
        """One ``sacct -j id1,id2,...`` invocation for the whole set —
        sacct accepts a comma-separated job list, so a 1000-job poll is one
        CLI startup instead of 1000."""
        if not job_ids:
            return {}
        out = subprocess.run(
            ["sacct", "-j", ",".join(str(j) for j in job_ids), "-X", "-n",
             "-o", "JobID%20,State%20"],
            capture_output=True, text=True, check=True,
        )
        states: dict[int, list[str]] = {j: [] for j in job_ids}
        for line in out.stdout.splitlines():
            parts = line.split()
            if len(parts) < 2:
                continue
            jid = parts[0].split("_")[0].split(".")[0]
            if jid.isdigit() and int(jid) in states:
                states[int(jid)].append(parts[1].rstrip("+"))
        return {j: fold_states(sts) for j, sts in states.items()}

    def sacct_tasks(self, job_id: int) -> list[str]:
        out = subprocess.run(
            ["sacct", "-j", str(job_id), "-n", "-o", "State%20"],
            capture_output=True, text=True, check=True,
        )
        return [s.strip() for s in out.stdout.splitlines() if s.strip()]

    def scancel(self, job_id: int) -> str | None:
        # real scancel is already idempotent on terminal jobs (exit 0)
        subprocess.run(["scancel", str(job_id)], check=True)
        return None

    def scontrol_update_dependency(
        self, job_id: int, add: list[int] | None = None,
        remove: list[int] | None = None, hold: bool = False,
    ) -> bool:
        # hold FIRST: 'scontrol update Dependency=' replaces the whole
        # expression, and a job left momentarily dependency-free before a
        # later hold would be eligible to start — defeating the
        # detach-and-hold invariant reschedule_straggler relies on
        if hold:
            if subprocess.run(["scontrol", "hold", str(job_id)]).returncode != 0:
                return False
        ok = self._rewrite_dependency(job_id, add or [], remove or [])
        if not ok and hold:
            # don't leave a stray user hold on a job we failed to rewire
            subprocess.run(["scontrol", "release", str(job_id)])
        return ok

    def _rewrite_dependency(
        self, job_id: int, add: list[int], remove: list[int]
    ) -> bool:
        # real scontrol REPLACES the Dependency expression: read the
        # current one and write back current - remove + add so a
        # remove-only call keeps the job's other afterok parents (and any
        # non-afterok clauses) instead of clearing them
        out = subprocess.run(
            ["scontrol", "show", "job", str(job_id)],
            capture_output=True, text=True,
        )
        if out.returncode != 0:
            return False
        state, expr = "", ""
        for tok in out.stdout.split():
            if tok.startswith("JobState="):
                state = tok.split("=", 1)[1]
            elif tok.startswith("Dependency="):
                expr = tok.split("=", 1)[1]
        if state != PENDING:
            return False  # started/finished jobs cannot be rewired
        afterok: list[int] = []
        others: list[str] = []
        if expr not in ("", "(null)"):
            for clause in expr.split(","):
                kind, _, rest = clause.partition(":")
                if kind == "afterok":
                    # newer Slurm annotates ids, e.g. afterok:123(unfulfilled)
                    ids = [p.partition("(")[0] for p in rest.split(":")]
                    afterok += [int(p) for p in ids if p.isdigit()]
                else:
                    others.append(clause)
        keep = [i for i in afterok if i not in set(remove)]
        keep += [a for a in add if a not in keep]
        clauses = others + (
            ["afterok:" + ":".join(str(i) for i in keep)] if keep else []
        )
        return subprocess.run(
            ["scontrol", "update", f"JobId={job_id}",
             f"Dependency={','.join(clauses)}"],
        ).returncode == 0

    def scontrol_release(self, job_id: int) -> None:
        subprocess.run(["scontrol", "release", str(job_id)], check=True)

    def wait(self, job_ids: list[int] | None = None, timeout: float = 300.0) -> None:
        deadline = time.time() + timeout
        ids = list(job_ids or [])
        while time.time() < deadline:
            if all(s in TERMINAL for s in self.sacct_many(ids).values()):
                return
            time.sleep(5.0)
        raise TimeoutError(f"jobs {ids} still running after {timeout}s")
