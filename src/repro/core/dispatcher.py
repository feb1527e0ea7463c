"""Dispatcher + job wrapper (paper §2).

The dispatcher "initiates the execution of a task on the selected resource
as per the scheduler's instruction [and] periodically updates the status of
task execution to the parametric-engine".  The job wrapper "is responsible
for staging of application tasks and data; starting execution ... and
sending results back".

Two executors implement the same contract:

* ``SimulatedExecutor`` — runs the wrapper phases (stage-in, execute,
  stage-out) in virtual time on the DES, honoring resource failures.
* ``LocalExecutor``     — runs real Python payloads (e.g. jit'd train
  steps) on a thread pool; used by the end-to-end examples where the
  "grid" is this machine.

Closed clusters route staging through ``StagingProxy`` (paper §4's
master-node GASS proxy).
"""
from __future__ import annotations

import dataclasses
import threading
import time
import traceback
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, Optional, Union

from repro.core.jobs import Job, JobStatus
from repro.core.resources import ResourceDirectory
from repro.core.simulator import Simulator, duration_model


class StagingProxy:
    """Master-node mediator for closed clusters: all stage traffic flows
    through it; it counts bytes (and in the DES costs 2x time, modeled in
    duration_model)."""

    def __init__(self):
        self.bytes_in = 0
        self.bytes_out = 0
        self.transfers = 0

    def stage(self, n_bytes: int, inbound: bool) -> None:
        self.transfers += 1
        if inbound:
            self.bytes_in += n_bytes
        else:
            self.bytes_out += n_bytes


# Reason string the executors report when a dispatch loses the race for
# the last free slot to a rival broker.  Distinct from a real failure: the
# resource is healthy, the job should simply requeue (no attempt burned,
# no suspicion cast on the resource).
SLOT_LOST = "slot contention: lost race for free slot"

# Reason reported when a whole site departs mid-run (churn) and takes its
# in-flight jobs with it.  Like every "resource ..." reason it is the
# machine's fault, not the job's: requeue without burning an attempt.
RESOURCE_DEPARTED = "resource departed: site left the grid"


def is_resource_fault(reason: str) -> bool:
    """True for failures caused by the resource dying or leaving (as
    opposed to the job's own payload failing).  A broker scheduling
    against a stale information-service view *will* dispatch to corpses;
    those burned dispatches requeue like ``SLOT_LOST`` — suspicion is
    cast on the resource, never an attempt charged to the job."""
    return reason.startswith("resource ")


@dataclasses.dataclass
class DispatchCallbacks:
    on_started: Callable[[Job], None]
    on_done: Callable[[Job, float], None]        # (job, exec_seconds)
    on_failed: Callable[[Job, str], None]        # (job, reason)
    on_blocked: Optional[Callable[[Job, str], None]] = None  # slot races

    def blocked(self, job: Job, reason: str) -> None:
        (self.on_blocked or self.on_failed)(job, reason)


class SimulatedExecutor:
    """Job-wrapper phases in virtual time, failure-aware.

    ``dispatch_latency`` models the WAN hop between a broker's decision
    and the remote queue actually granting the slot — with it non-zero,
    two brokers that decided in the same scheduling round genuinely race
    for the last slot and one of them loses (gets ``SLOT_LOST``)."""

    def __init__(self, sim: Simulator, directory: ResourceDirectory,
                 seed: Union[int, str] = 0, noise_sigma: float = 0.15,
                 dispatch_latency: float = 0.0):
        self.sim = sim
        self.directory = directory
        self.seed = seed
        self.noise_sigma = noise_sigma
        self.dispatch_latency = dispatch_latency
        self.proxy = StagingProxy()
        self.slot_races_lost = 0
        self._running: Dict[str, dict] = {}    # job_id -> {cancelled: bool}
        # independent per-resource count of slots this executor holds,
        # maintained at exactly the acquire/release sites.  The online
        # slot-accounting watchdog cross-checks it against the
        # directory's ``running`` book in O(1) per resource — a rogue
        # release moves one book but not the other
        self._held: Dict[str, int] = {}

    def submit(self, job: Job, resource: str, cb: DispatchCallbacks) -> None:
        # register the cancel token BEFORE the latency hop: a duplicate
        # killed while still in flight must never acquire a slot and run
        token = {"cancelled": False, "job": job, "cb": cb,
                 "resource": resource}
        self._running[job.job_id] = token
        if self.dispatch_latency > 0.0:
            self.sim.after(
                self.dispatch_latency,
                lambda: self._acquire_and_run(job, resource, cb, token))
        else:
            self._acquire_and_run(job, resource, cb, token)

    def _drop_token(self, job: Job, token: dict) -> None:
        if self._running.get(job.job_id) is token:
            del self._running[job.job_id]

    def _acquire_and_run(self, job: Job, resource: str,
                         cb: DispatchCallbacks, token: dict) -> None:
        if token["cancelled"]:          # killed while in the WAN hop
            self._drop_token(job, token)
            return
        spec = self.directory.spec(resource)
        st = self.directory.status(resource)
        if not st.up:
            self._drop_token(job, token)
            cb.on_failed(job, RESOURCE_DEPARTED if st.departed
                         else "resource unavailable at submit")
            return
        if not st.acquire(spec):
            self._drop_token(job, token)
            self.slot_races_lost += 1
            cb.blocked(job, SLOT_LOST)
            return
        job.slot_held = True
        self._held[resource] = self._held.get(resource, 0) + 1
        job.acquired_at = self.sim.now
        s_in, ex, s_out = duration_model(
            spec, job.spec.est_seconds_base, job.spec.stage_in_bytes,
            job.spec.stage_out_bytes, load=st.load,
            noise_sigma=self.noise_sigma,
            seed=(self.seed, job.job_id, job.attempt, resource))
        if spec.closed:
            self.proxy.stage(job.spec.stage_in_bytes, inbound=True)

        def _fail_if_down(phase_next: Callable[[], None], reason: str):
            def wrapped():
                if token["cancelled"]:
                    self._finish(job, spec.name, token)
                    return
                if not self.directory.status(resource).up:
                    self._finish(job, spec.name, token)
                    cb.on_failed(job, reason)
                    return
                phase_next()
            return wrapped

        def start_exec():
            cb.on_started(job)
            self.sim.after(ex, _fail_if_down(do_stage_out,
                                             "resource failed during run"))

        def do_stage_out():
            if spec.closed:
                self.proxy.stage(job.spec.stage_out_bytes, inbound=False)
            self.sim.after(s_out, _fail_if_down(finish,
                                                "resource failed staging out"))

        def finish():
            self._finish(job, spec.name, token)
            cb.on_done(job, ex)

        self.sim.after(s_in, _fail_if_down(start_exec,
                                           "resource failed staging in"))

    def _finish(self, job: Job, resource: str, token: dict) -> None:
        # idempotent AND token-gated: interrupt() may finish a job whose
        # phase timers are still in the heap, and the engine may have
        # redispatched the same job since — a late closure holding the
        # old token must neither pop the new token nor release the slot
        # the new dispatch acquired
        if self._running.get(job.job_id) is not token:
            return
        del self._running[job.job_id]
        if job.slot_held:
            job.slot_held = False
            self._held[resource] -= 1
            self.directory.status(resource).release()

    def cancel(self, job: Job) -> None:
        tok = self._running.get(job.job_id)
        if tok:
            tok["cancelled"] = True

    def interrupt(self, resource: str,
                  reason: str = RESOURCE_DEPARTED) -> int:
        """Fail over everything in flight on ``resource`` RIGHT NOW —
        a departing site does not wait for phase boundaries.  Slots are
        released, callbacks fire immediately (jobs still in the WAN hop
        included: their dispatch was racing toward a corpse), and the
        phase timers already in the heap become no-ops.  Returns the
        number of dispatches failed over."""
        victims = [tok for jid, tok in sorted(self._running.items())
                   if tok["resource"] == resource and not tok["cancelled"]]
        for tok in victims:
            tok["cancelled"] = True
            self._finish(tok["job"], resource, tok)
            tok["cb"].on_failed(tok["job"], reason)
        return len(victims)

    def estimate(self, job: Job, resource: str) -> float:
        spec = self.directory.spec(resource)
        s_in, ex, s_out = duration_model(
            spec, job.spec.est_seconds_base, job.spec.stage_in_bytes,
            job.spec.stage_out_bytes, load=self.directory.status(resource).load,
            noise_sigma=0.0, seed=())
        return s_in + ex + s_out


class LocalExecutor:
    """Real execution: ``job.spec.payload`` is a callable() -> result."""

    def __init__(self, directory: ResourceDirectory, max_workers: int = 4):
        self.directory = directory
        self.pool = ThreadPoolExecutor(max_workers=max_workers)
        self.proxy = StagingProxy()
        self._futures: Dict[str, Future] = {}
        self._lock = threading.Lock()

    def submit(self, job: Job, resource: str, cb: DispatchCallbacks) -> None:
        spec = self.directory.spec(resource)
        st = self.directory.status(resource)
        with self._lock:
            if not st.up:
                cb.on_failed(job, "resource unavailable at submit")
                return
            if not st.acquire(spec):
                cb.blocked(job, SLOT_LOST)
                return
            job.slot_held = True
            job.acquired_at = time.time()

        def run():
            cb.on_started(job)
            t0 = time.monotonic()
            try:
                job.result = (job.spec.payload() if callable(job.spec.payload)
                              else None)
            except Exception as e:  # noqa: BLE001 — job failure, not ours
                with self._lock:
                    job.slot_held = False
                    st.release()
                # the whole traceback rides in the reason: the journal's
                # FAIL record is the only place a payload failure survives
                cb.on_failed(job, f"payload raised: {e!r}\n"
                                  f"{traceback.format_exc()}")
                return
            with self._lock:
                job.slot_held = False
                st.release()
            cb.on_done(job, time.monotonic() - t0)

        self._futures[job.job_id] = self.pool.submit(run)

    def cancel(self, job: Job) -> None:
        f = self._futures.get(job.job_id)
        if f:
            f.cancel()

    def estimate(self, job: Job, resource: str) -> float:
        spec = self.directory.spec(resource)
        return job.spec.est_seconds_base / max(spec.perf_factor, 1e-6)

    def shutdown(self) -> None:
        self.pool.shutdown(wait=True)


class Dispatcher:
    """Thin mediation layer the engine talks to (paper's component)."""

    def __init__(self, executor, directory: ResourceDirectory):
        self.executor = executor
        self.directory = directory
        self.dispatched = 0

    def dispatch(self, job: Job, resource: str, cb: DispatchCallbacks
                 ) -> None:
        job.resource = resource
        job.status = JobStatus.STAGED
        job.attempt += 1
        self.dispatched += 1
        self.executor.submit(job, resource, cb)

    def cancel(self, job: Job) -> None:
        self.executor.cancel(job)

    def estimate(self, job: Job, resource: str) -> float:
        return self.executor.estimate(job, resource)
