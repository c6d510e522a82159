"""Fleet-scale optimization engine: concurrent multi-kernel scheduling over
a transfer-aware result store.

The paper runs Xe-Forge over 97 KernelBench-L2 kernels; at that scale the
single-kernel ``ForgePipeline.optimize`` loop wastes most of its work —
structurally identical programs (the GEMM family differs only in node labels)
re-run the full nine-stage CoVeR search from scratch, strictly sequentially.
The :class:`OptimizationEngine` fixes both axes:

* **Batching + pluggable execution backends** — jobs are scheduled across an
  executor selected by ``ForgeConfig.execution_backend``:

  - ``serial`` — in submission order on the calling thread (the
    deterministic reference mode);
  - ``thread`` — a bounded thread pool (the default; cheap, but the
    interpreter-heavy verify path contends on the GIL);
  - ``process`` — spawned worker processes, each owning a private pipeline
    built from the picklable :class:`ForgeConfig`; jobs travel as the
    :mod:`repro.core.job_codec` wire form and results/observer events stream
    back through a results queue.
  - ``remote`` — the same tagged worker protocol over TCP
    (:mod:`repro.core.fleet`): a ``FleetCoordinator`` dispatches to N
    ``forge-worker`` processes — loopback-spawned or connected from other
    hosts — with heartbeat loss detection and automatic re-dispatch of
    in-flight jobs.

  All four are **result-equivalent**: cache keys, transform logs, and
  optimized schedules are identical whichever backend ran a batch (results
  always come back in submission order, priors are frozen once per batch and
  transfer seeds once per phase). ``scripts/backend_equivalence.py`` gates
  this in CI.

* **Exact replay** — the :class:`ResultStore` (``repro.core.result_store``)
  keys entries on the canonical structural fingerprint of (graph, schedule,
  spec, tolerances) (:mod:`repro.ir.fingerprint`) *plus the KB content hash*
  — editing any KB YAML invalidates recorded sequences instead of replaying
  them forever. A hit replays the recorded :class:`TransformLog` — one
  verification per accepted transform instead of the full proposal search —
  and cross-checks that the replayed schedule is bit-identical to the cached
  canonical schedule. Any divergence falls back, so the cache can never
  produce a wrong result, only a slower path.

* **Family transfer** — on an exact miss, the rank-abstracted *family*
  fingerprint (same builder, different dims) is probed; a neighbor's log is
  handed to the stage scheduler as a speculative warm start
  (``StageScheduler.apply_seed``): each logged step is verified on the
  job's real shapes and the full proposal search resumes from wherever the
  transfer diverges. This is the paper's "the underlying optimization
  patterns remain largely consistent" premise made operational.

* **Warm starts** — the shared :class:`History` records every stage outcome;
  its success-count priors reorder proposer candidates for subsequent
  batches (see ``StageScheduler``). Process workers record to private
  histories whose records ride the results queue back and merge into the
  parent's history, so multi-batch warm starts stay backend-equivalent.
"""

from __future__ import annotations

import dataclasses
import hashlib
import multiprocessing
import pathlib
import pickle
import queue as queue_mod
import threading
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Mapping, Optional, Sequence

from repro.core import job_codec
from repro.core.config import EXECUTION_BACKENDS
from repro.core.observers import (JobEvent, StageEvent, TransferEvent,
                                  as_observer)
from repro.core.pipeline import ForgePipeline, PipelineResult, prepare_oracle
from repro.core.result_store import ResultCache, ResultStore
from repro.core.stage_scheduler import TransformLog
from repro.core.verify_cache import (SharedVerifyCache, VerifySession,
                                     run_program_cached)
from repro.ir.fingerprint import (fingerprint_family,
                                  fingerprint_family_ladder, fingerprint_job,
                                  job_dims_vector, program_canonical,
                                  program_exec_fingerprint)
from repro.ir.schedule import KernelProgram

__all__ = ["KernelJob", "EngineResult", "EngineStats", "VerifyStats",
           "OptimizationEngine", "ResultCache", "ResultStore", "execute_job",
           "replay_entry", "entry_for_result", "compute_job_keys",
           "fold_worker_result"]


@dataclasses.dataclass
class KernelJob:
    """One named optimization unit: the ci-shaped program the verifier
    executes and the bench-shaped program the cost model scores."""

    name: str
    ci_program: KernelProgram
    bench_program: KernelProgram
    tags: tuple = ()
    target_dtype: str = "bfloat16"
    rtol: float = 1e-2
    atol: float = 1e-5
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def fingerprint(self, spec_name: str, policy: str = "") -> str:
        return fingerprint_job(self.ci_program, self.bench_program,
                               spec_name, self.target_dtype,
                               self.rtol, self.atol, self.tags,
                               meta=self.meta, policy=policy)

    def family_fingerprint(self, spec_name: str, policy: str = "") -> str:
        """Rank-abstracted key: same builder at different dims collides."""
        return fingerprint_family(self.ci_program, self.bench_program,
                                  spec_name, self.target_dtype, self.tags,
                                  meta=self.meta, policy=policy)

    def family_ladder(self, spec_name: str, policy: str = "") -> tuple:
        """Graded ``((tier, key), ...)`` transfer keys, finest tier first;
        the last pair is exactly :meth:`family_fingerprint`."""
        return fingerprint_family_ladder(self.ci_program, self.bench_program,
                                         spec_name, self.target_dtype,
                                         self.tags, meta=self.meta,
                                         policy=policy)

    def dims_vector(self) -> tuple:
        """Concrete shape-extent vector for neighbor distance ranking."""
        return job_dims_vector(self.ci_program, self.bench_program)


@dataclasses.dataclass
class EngineResult:
    job: KernelJob
    result: PipelineResult
    fingerprint: str
    cache_hit: bool = False
    transfer: bool = False          # warm-started from a family neighbor
    seed_steps: int = 0             # neighbor steps that verified and stuck
    replay_fallback: bool = False   # exact hit whose replay diverged
    had_seed: bool = False          # a family seed was available for the run
    # the job's VerifySessionStats dict (None when the fast path is off):
    # lets a caller rebuild exactly the per-job stats delta the engine folded
    # into its lifetime counters (see OptimizationReport.from_result)
    verify: Optional[Dict[str, int]] = None


@dataclasses.dataclass
class EngineStats:
    jobs: int = 0
    cache_hits: int = 0             # exact fingerprint hit, replay succeeded
    cache_misses: int = 0           # full runs: no exact entry OR replay
                                    # diverged (those also count a fallback)
    replay_fallbacks: int = 0       # exact hit but replay diverged
    family_transfers: int = 0       # exact miss, neighbor seed (partially) applied
    transfer_fallbacks: int = 0     # neighbor found but no seed step applied

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class VerifyStats:
    """Engine-lifetime aggregate of the per-job ``VerifySessionStats`` plus
    the batch planner's counters (all flat ints, so facade-level batch
    deltas subtract field-wise like :class:`EngineStats`).

    Deliberately a separate object from :class:`EngineStats`: the backend-
    equivalence contract asserts EngineStats bit-identical across backends,
    but shared-cache hit counts legitimately differ — serial/thread sessions
    read one live engine-owned cache, while process workers see private
    per-worker caches warmed only by the planner's shipped slice. The
    *results* stay identical either way (a shared miss just re-executes);
    only the accounting of where an execution was saved moves."""

    group_hits: int = 0
    group_misses: int = 0
    trace_hits: int = 0
    trace_misses: int = 0
    structure_hits: int = 0
    structure_misses: int = 0
    cost_hits: int = 0
    cost_misses: int = 0
    oracle_hits: int = 0
    oracle_misses: int = 0
    screened: int = 0
    deferred_runs: int = 0
    shared_group_hits: int = 0      # group execs served by the shared layer
    shared_oracle_hits: int = 0     # oracle preps rebound from it
    exec_passed: int = 0            # correctness runs that matched the oracle
    exec_errors: int = 0            # correctness runs that raised
    planner_signatures: int = 0     # duplicated slices the planner executed
    planner_deduped_jobs: int = 0   # follower jobs that started warm
    planner_group_execs: int = 0    # group execs the planner paid up front
    planner_oracle_preps: int = 0   # oracle preps the planner paid up front

    def add_session(self, session_stats: Mapping[str, int]):
        for k, v in session_stats.items():
            if hasattr(self, k):
                setattr(self, k, getattr(self, k) + int(v))

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


# ----------------------------------------------------------------------
# Backend-independent single-job execution.
#
# These module-level functions are the one implementation of "optimize this
# job against this pipeline" that every backend shares: the serial loop and
# the thread pool call them against the engine's own pipeline; a process
# worker calls them against its private pipeline rebuilt from the shipped
# ForgeConfig. Keeping them free of engine state is what makes the three
# backends result-equivalent by construction.
# ----------------------------------------------------------------------

def compute_job_keys(pipeline: ForgePipeline, job: KernelJob) -> tuple:
    """(exact store key, family key, family ladder, dims vector) for a job
    against a pipeline. The exact key folds in the KB content hash so a KB
    edit turns every previously-exact hit into a miss; the transfer keys
    deliberately do not (transferred seeds are re-verified step-by-step, so
    stale ones are safe, just weaker). Transfer keys are also scoped by the
    *transfer* policy signature, which excludes search-order knobs — so
    stores written under the pre-knob signature stay transferable. The
    family key is the ladder's coarsest ("rank") tier.

    Module-level on purpose: the parent engine and spawned workers must
    derive bit-identical keys from the same inputs (the job codec's wire
    form round-trips fingerprints exactly), so the executors can push this
    — the serial chunk of dispatch — down into their workers."""
    spec = pipeline.spec.name
    policy = pipeline.policy_signature()
    fp = job.fingerprint(spec, policy)
    kb_hash = pipeline.kb.content_hash()
    exact = hashlib.sha256(f"{fp}|kb={kb_hash}".encode()).hexdigest()
    ladder = job.family_ladder(spec, pipeline.transfer_policy_signature())
    return exact, ladder[-1][1], ladder, job.dims_vector()


def entry_for_result(result: PipelineResult) -> Dict[str, Any]:
    """The result-store entry recording a cold run's winning sequence."""
    return {
        "name": result.name,
        "transform_log": (result.transform_log.to_list()
                          if result.transform_log else []),
        "canonical_schedule": program_canonical(
            result.bench_program)["schedule"],
        "original_time": result.original_time,
        "optimized_time": result.optimized_time,
        # never-degrade fired on the cold run: replay must reproduce the
        # clamp instead of treating final_time > original as divergence
        "clamped": result.clamped,
    }


def replay_entry(pipeline: ForgePipeline, job: KernelJob,
                 entry: Dict[str, Any],
                 priors: Mapping[str, int],
                 session=None, on_stage=None) -> Optional[PipelineResult]:
    """Replay a cached transform log onto this job's programs. Returns
    None (-> full optimization) on any divergence, including a replayed
    schedule that is not bit-identical to the cached canonical form.
    ``session`` is the job's verification memo: shared with the
    full-optimization fallback so a diverged replay's oracle prep and
    verified prefix are not paid for twice. ``on_stage`` is the per-job
    stage observer (replayed steps emit stage records too)."""
    log = TransformLog.from_list(entry.get("transform_log", []))
    ctx = pipeline._prepare_ctx(job.name, job.ci_program, job.tags,
                                job.target_dtype, job.rtol, job.atol,
                                job.meta or {}, session=session)
    original_cost = pipeline.cost_model.program_cost(job.bench_program)
    scheduler = pipeline.make_scheduler(
        priors, session=session,
        on_stage_complete=pipeline.stage_hook(on_stage))
    out = scheduler.replay(log, job.ci_program.copy(),
                           job.bench_program.copy(), ctx)
    if out is None:
        return None
    ci_prog, bench_prog, records = out
    got = program_canonical(bench_prog)["schedule"]
    if got != entry.get("canonical_schedule"):
        return None
    final_time = pipeline.cost_model.program_time(bench_prog)
    if final_time > original_cost.total_s:
        if not entry.get("clamped"):
            return None
        # reproduce the cold run's never-degrade clamp
        return PipelineResult(job.name, original_cost.total_s,
                              original_cost.total_s, ci_prog, bench_prog,
                              records, [], transform_log=log,
                              cache_hit=True, clamped=True)
    return PipelineResult(job.name, original_cost.total_s, final_time,
                          ci_prog, bench_prog, records, [],
                          transform_log=log, cache_hit=True)


def execute_job(pipeline: ForgePipeline, job: KernelJob,
                entry: Optional[Dict[str, Any]],
                seed_pairs: Sequence,
                exact_key: str,
                priors: Mapping[str, int],
                shared: Optional[SharedVerifyCache] = None,
                on_stage=None):
    """Replay-or-optimize one job. ``entry`` is the exact store entry (or
    None); ``seed_pairs`` is the frozen ``(neighbor_key, log_list)`` graded
    family-ladder snapshot for this job's phase (closest neighbor first); ``shared`` is the cross-job verification
    cache the job's session reads through / writes back (engine-owned on
    the in-process backends, per-worker on the process backend). Returns
    ``(PipelineResult, outcome)`` where ``outcome`` carries the store/stat
    flags::

        {"cache_hit", "replay_fallback", "had_seed", "transferred",
         "entry",    # entry: dict to store, or None on a replayed hit
         "verify"}   # the session's VerifySessionStats dict, or None
    """
    outcome = {"cache_hit": False, "replay_fallback": False,
               "had_seed": False, "transferred": False, "entry": None,
               "verify": None}
    # one verification memo for the job's whole lifecycle: replay attempt,
    # transfer seeding, and the full search all share it
    session = pipeline.make_verify_session(shared=shared)
    if entry is not None:
        replayed = replay_entry(pipeline, job, entry, priors,
                                session=session, on_stage=on_stage)
        if replayed is not None:
            outcome["cache_hit"] = True
            if session is not None:
                outcome["verify"] = session.stats.as_dict()
            return replayed, outcome
        outcome["replay_fallback"] = True

    # exact miss (or diverged replay): probe the phase's frozen family
    # snapshot for a transfer seed. The job's own exact entry is
    # excluded — when its replay just diverged, re-seeding from the very
    # log that failed cannot help — but another family member still can.
    seed_log: Optional[TransformLog] = None
    for neighbor_key, log_list in seed_pairs:
        if neighbor_key != exact_key and log_list:
            seed_log = TransformLog.from_list(log_list)
            break

    result = pipeline.optimize(
        job.name, job.ci_program, job.bench_program, tags=job.tags,
        target_dtype=job.target_dtype, rtol=job.rtol, atol=job.atol,
        meta=job.meta, priors=priors, seed_log=seed_log, session=session,
        on_stage=on_stage)
    outcome["entry"] = entry_for_result(result)
    outcome["had_seed"] = seed_log is not None
    outcome["transferred"] = (seed_log is not None
                              and result.seed_steps_applied > 0)
    if session is not None:
        outcome["verify"] = session.stats.as_dict()
    return result, outcome


def fold_worker_result(engine: "OptimizationEngine", job: KernelJob,
                       keys: tuple, payload: Mapping[str, Any],
                       notify=None) -> EngineResult:
    """Fold one worker's result payload (``{"result", "entry", "outcome",
    "history"}`` — the wire shape both process and remote workers return)
    into the parent engine: store the entry, apply the outcome to the
    stats, decode the result, and notify. The caller merges the history
    delta (in submission order, after its whole wave lands). Shared by
    the process and remote executors so the two transports cannot drift
    in how results are merged — the parent stays the single owner of
    store/stats/history on every backend."""
    exact_key, family_key = keys[0], keys[1]
    outcome = payload["outcome"]
    if payload["entry"] is not None:
        engine.cache.put(exact_key, payload["entry"], family=family_key,
                         flush=False, ladder=keys[2], dims=keys[3])
    engine._apply_outcome(outcome)
    result = job_codec.decode_pipeline_result(payload["result"])
    eres = EngineResult(job, result, exact_key,
                        cache_hit=outcome["cache_hit"],
                        transfer=outcome["transferred"],
                        seed_steps=result.seed_steps_applied,
                        replay_fallback=outcome["replay_fallback"],
                        had_seed=outcome["had_seed"],
                        verify=outcome.get("verify"))
    engine._notify_result(eres, notify)
    return eres


# ----------------------------------------------------------------------
# Execution backends. Each runs one scheduling *phase* (the engine's
# leader/follower split) and writes EngineResults into ``results`` at the
# jobs' submission indices.
# ----------------------------------------------------------------------

class SerialExecutor:
    """In-order execution on the calling thread — the reference backend."""

    name = "serial"

    def __init__(self, engine: "OptimizationEngine"):
        self.engine = engine

    def compute_keys(self, jobs) -> List[tuple]:
        return [compute_job_keys(self.engine.pipeline, job) for job in jobs]

    def run_phase(self, jobs, phase, keys, priors, seeds, results,
                  plan=None, on_stage=None, notify=None):
        # plan is unused in-process: jobs read the engine-owned shared
        # cache directly, which the planner already pre-populated
        for i in phase:
            results[i] = self.engine._run_job(jobs[i], keys[i], priors,
                                              seeds.get(i, ()),
                                              on_stage=_index_stage_hook(
                                                  on_stage, i),
                                              notify=notify)

    def end_batch(self):
        pass

    def close(self):
        pass


def _index_stage_hook(on_stage, index: int):
    """Bind a batch-level ``on_stage(index, job_name, record)`` callback to
    one job's submission index — the per-job hook execute_job expects."""
    if on_stage is None:
        return None

    def hook(job_name, record):
        on_stage(index, job_name, record)
    return hook


class ThreadExecutor:
    """Bounded thread pool (``workers`` threads); single-job phases and
    ``workers=1`` degrade to the serial path."""

    name = "thread"

    def __init__(self, engine: "OptimizationEngine"):
        self.engine = engine

    def compute_keys(self, jobs) -> List[tuple]:
        # deliberately serial: key computation is GIL-bound pure-Python
        # (toposort + canonical JSON), so a thread fan-out only pays pool
        # overhead — the worker-side win is real on the process backend,
        # where workers hash in parallel interpreters
        return [compute_job_keys(self.engine.pipeline, job) for job in jobs]

    def run_phase(self, jobs, phase, keys, priors, seeds, results,
                  plan=None, on_stage=None, notify=None):
        # plan unused here too — threads share the live engine-owned cache
        engine = self.engine
        if engine.workers <= 1 or len(phase) <= 1:
            for i in phase:
                results[i] = engine._run_job(jobs[i], keys[i], priors,
                                             seeds.get(i, ()),
                                             on_stage=_index_stage_hook(
                                                 on_stage, i),
                                             notify=notify)
            return
        with ThreadPoolExecutor(max_workers=engine.workers) as pool:
            futures = [(i, pool.submit(engine._run_job, jobs[i], keys[i],
                                       priors, seeds.get(i, ()),
                                       _index_stage_hook(on_stage, i),
                                       notify))
                       for i in phase]
            for i, f in futures:
                results[i] = f.result()

    def end_batch(self):
        pass

    def close(self):
        pass


def _process_worker_main(config_dict: Dict[str, Any],
                         kb_blob: Optional[bytes],
                         task_q, event_q):
    """Worker process loop: rebuild a private pipeline from the shipped
    ForgeConfig (+ pickled KB), then serve tasks until the ``None``
    sentinel. Tasks are tagged tuples: ``("keys", idx, job_wire)`` computes
    the job's exact/family cache keys worker-side (the wire codec makes the
    fingerprints bit-exact across the spawn boundary, so parent and worker
    derive identical keys); ``("job", idx, ...)`` optimizes. Observer events
    are not dropped: every stage record streams back through the results
    queue as it happens, and each finished job returns its wire-encoded
    result, store entry, outcome flags, and the private history delta for
    the parent to merge.

    The worker owns a private :class:`SharedVerifyCache` that persists
    across its tasks (cross-job sharing *within* the worker); each job task
    may additionally carry a parent-side warm slice — the planner-recorded
    shared-cache entries for the job's oracle slice, wire-encoded by
    :mod:`repro.core.job_codec` — which is installed before the job runs so
    planner dedup survives the process boundary."""
    from repro.core.config import ForgeConfig
    from repro.core.history import History

    config = ForgeConfig.from_dict(config_dict)
    kb = pickle.loads(kb_blob) if kb_blob else None
    pipeline = ForgePipeline.from_config(config, kb=kb)
    shared = None
    if (config.shared_verify_cache_bytes > 0
            and config.verify_fastpath != "off"):
        shared = SharedVerifyCache(config.shared_verify_cache_bytes)
    while True:
        task = task_q.get()
        if task is None:
            return
        kind, idx = task[0], task[1]
        try:
            if kind == "keys":
                job = job_codec.decode_job(task[2])
                event_q.put(("keys", idx, compute_job_keys(pipeline, job)))
                continue
            _, _, job_wire, exact_key, family_key, priors_wire, entry, \
                seed_pairs, warm_wire = task
            job = job_codec.decode_job(job_wire)
            priors = job_codec.decode_priors(priors_wire)
            if warm_wire is not None and shared is not None:
                for key, value in job_codec.decode_verify_slice(warm_wire):
                    shared.put(key, value)
            # fresh per-task history: the records travel back with the
            # result and merge into the parent's shared history, instead of
            # accumulating invisibly (and divergently) per worker
            pipeline.history = History()
            pipeline.on_stage_complete = (
                lambda name, rec, _idx=idx: event_q.put(
                    ("stage", _idx, name, job_codec.encode_stage_record(rec))))
            result, outcome = execute_job(pipeline, job, entry, seed_pairs,
                                          exact_key, priors, shared=shared)
            event_q.put(("result", idx, {
                "result": job_codec.encode_pipeline_result(result),
                "entry": outcome.pop("entry"),
                "outcome": outcome,
                "history": list(pipeline.history.records),
            }))
        except Exception:  # noqa: BLE001 — marshal the traceback up
            event_q.put(("error", idx, traceback.format_exc()))


class ProcessExecutor:
    """Spawned worker processes, each owning a private pipeline.

    The parent stays the single owner of the result store, the stats, the
    shared history, and observer dispatch: workers only ever see one job at
    a time plus the frozen seeds for it, and everything they produce —
    stage events, results, store entries, history records — flows back
    through one results queue. The ``spawn`` start method is used
    unconditionally (fork + JAX is a deadlock lottery), which is exactly why
    the :mod:`repro.core.job_codec` wire form exists."""

    name = "process"

    def __init__(self, engine: "OptimizationEngine"):
        if engine.pipeline.llm is not None:
            raise ValueError(
                "execution_backend='process' cannot ship a live LLM client "
                "to worker processes; use the 'thread' backend")
        self.engine = engine
        self._ctx = multiprocessing.get_context("spawn")
        self._task_q = None
        self._event_q = None
        self._procs: List = []
        # wire forms encoded once per batch by compute_keys and reused by
        # the job waves (keyed on the jobs-list identity so an interleaved
        # batch safely falls back to encoding); cleared by end_batch so a
        # finished batch neither pins its encodings nor can alias a future
        # jobs list that lands on a recycled id
        self._wires: Optional[tuple] = None     # (id(jobs), [wire, ...])
        # one phase at a time through the shared queues: two concurrent
        # run_batch calls must never drain each other's events (the serial/
        # thread paths tolerate overlap via the _inflight locks; here the
        # queues are the shared resource, so overlapping callers queue up)
        self._phase_lock = threading.Lock()

    # ------------------------------------------------------------------
    def _ensure_pool(self):
        self._procs = [p for p in self._procs if p.is_alive()]
        if self._procs:
            return
        engine = self.engine
        self._task_q = self._ctx.Queue()
        self._event_q = self._ctx.Queue()
        config_dict = engine.pipeline.config.to_dict()
        kb_blob = pickle.dumps(engine.pipeline.kb)
        n = max(1, engine.workers)
        self._procs = [
            self._ctx.Process(target=_process_worker_main,
                              args=(config_dict, kb_blob, self._task_q,
                                    self._event_q),
                              daemon=True, name=f"forge-worker-{i}")
            for i in range(n)
        ]
        for p in self._procs:
            p.start()

    def _next_event(self):
        while True:
            try:
                return self._event_q.get(timeout=1.0)
            except queue_mod.Empty:
                if any(not p.is_alive() for p in self._procs):
                    try:  # drain anything the dying worker still flushed
                        return self._event_q.get_nowait()
                    except queue_mod.Empty:
                        raise RuntimeError(
                            "process backend worker died mid-batch "
                            "(see stderr for the worker traceback)")

    # ------------------------------------------------------------------
    def compute_keys(self, jobs) -> List[tuple]:
        """Fan the per-job fingerprint/key computation out to the worker
        pool — the ROADMAP's 'parent computes cache keys serially before
        dispatch' bottleneck. The phase lock keeps a concurrent run_batch
        from draining this wave's events."""
        with self._phase_lock:
            try:
                self._ensure_pool()
                wires = [job_codec.encode_job(job) for job in jobs]
                self._wires = (id(jobs), wires)
                keys: List[Optional[tuple]] = [None] * len(jobs)
                for i in range(len(jobs)):
                    self._task_q.put(("keys", i, wires[i]))
                pending = set(range(len(jobs)))
                while pending:
                    event = self._next_event()
                    if event[0] == "keys":
                        _, idx, pair = event
                        keys[idx] = tuple(pair)
                        pending.discard(idx)
                    else:  # "error" (stage/result events can't occur here)
                        _, idx, tb = event
                        raise RuntimeError(
                            f"process backend key computation for job "
                            f"#{idx} failed in worker:\n{tb}")
                return keys
            except Exception:
                self.close()
                raise

    # ------------------------------------------------------------------
    def run_phase(self, jobs, phase, keys, priors, seeds, results,
                  plan=None, on_stage=None, notify=None):
        with self._phase_lock:
            try:
                self._ensure_pool()
                # duplicate exact keys within a phase run as a second wave:
                # the first occurrence computes, the wave boundary puts its
                # entry in the store, and the duplicates replay — the same
                # 1-full-run + N-1-replays the _inflight locks give the
                # in-process backends, so cache_hit stays backend-equivalent
                seen = set()
                waves: List[List[int]] = [[], []]
                for i in phase:
                    waves[1 if keys[i][0] in seen else 0].append(i)
                    seen.add(keys[i][0])
                for wave in waves:
                    if wave:
                        self._run_wave(jobs, wave, keys, priors, seeds,
                                       results, plan, on_stage=on_stage,
                                       notify=notify)
            except Exception:
                # anything unexpected (a raising observer, a decode error, a
                # dead worker) leaves undispatched tasks / undrained events
                # behind; tear the pool down so the next batch starts clean
                # instead of consuming this batch's leftovers
                self.close()
                raise

    def _run_wave(self, jobs, wave, keys, priors, seeds, results, plan=None,
                  on_stage=None, notify=None):
        engine = self.engine
        wires = (self._wires[1] if self._wires
                 and self._wires[0] == id(jobs) else None)
        pending: Dict[int, KernelJob] = {}
        for i in wave:
            exact_key, family_key = keys[i][0], keys[i][1]
            wire = wires[i] if wires else job_codec.encode_job(jobs[i])
            # warm slice: the planner-recorded shared-cache entries for this
            # job's oracle slice, snapshotted parent-side at dispatch — the
            # worker's private cache cannot see the parent's, so the slice
            # rides the task (entries already evicted are simply skipped)
            warm_wire = None
            if plan and plan.get(i) and engine.verify_shared is not None:
                items = [(key, val) for key in plan[i]
                         if (val := engine.verify_shared.get(key)) is not None]
                if items:
                    warm_wire = job_codec.encode_verify_slice(items)
            self._task_q.put(("job", i, wire,
                              exact_key, family_key,
                              job_codec.encode_priors(priors),
                              engine.cache.get(exact_key),
                              list(seeds.get(i, ())), warm_wire))
            pending[i] = jobs[i]
        history_records: Dict[int, List[dict]] = {}
        while pending:
            event = self._next_event()
            kind = event[0]
            if kind == "stage":
                _, idx, job_name, record = event
                hook = engine.pipeline.on_stage_complete
                if hook is not None or on_stage is not None:
                    decoded = job_codec.decode_stage_record(record)
                    if hook is not None:
                        hook(job_name, decoded)
                    if on_stage is not None:
                        on_stage(idx, job_name, decoded)
            elif kind == "result":
                _, idx, payload = event
                eres = fold_worker_result(engine, pending.pop(idx),
                                          keys[idx], payload, notify=notify)
                history_records[idx] = payload["history"]
                results[idx] = eres
            else:  # "error"
                _, idx, tb = event
                raise RuntimeError(
                    f"process backend job #{idx} failed in worker:\n{tb}")
        # merge worker history deltas in submission order: counts are
        # additive (order-independent), the record list stays deterministic
        for i in sorted(history_records):
            engine.pipeline.history.merge_records(history_records[i])

    # ------------------------------------------------------------------
    def end_batch(self):
        self._wires = None

    def close(self):
        self._wires = None
        procs, self._procs = self._procs, []
        if not procs:
            return
        for p in procs:
            if p.is_alive() and self._task_q is not None:
                try:
                    self._task_q.put(None)
                except (ValueError, OSError):
                    break
        for p in procs:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()
                p.join(timeout=1)
        for q in (self._task_q, self._event_q):
            if q is not None:
                q.close()
                q.cancel_join_thread()
        self._task_q = self._event_q = None


class _RecordingSharedCache:
    """Planner-side wrapper over a :class:`SharedVerifyCache` that records
    every key its session touched (reads that hit + successful writes), in
    first-touch order — exactly the warm slice the process backend must
    ship to the planned jobs."""

    def __init__(self, inner: SharedVerifyCache):
        self._inner = inner
        self.keys: List[tuple] = []
        self._seen: set = set()

    def _note(self, key: tuple):
        if key not in self._seen:
            self._seen.add(key)
            self.keys.append(key)

    def get(self, key: tuple):
        got = self._inner.get(key)
        if got is not None:
            self._note(key)
        return got

    def put(self, key: tuple, value) -> bool:
        ok = self._inner.put(key, value)
        if ok:
            self._note(key)
        return ok


def _remote_executor(engine: "OptimizationEngine"):
    """Lazy factory for the distributed-fleet executor. The fleet module
    imports this one (for the worker protocol pieces), so registering the
    class directly would be an import cycle; a runtime import is also what
    keeps the socket stack out of every non-remote process."""
    from repro.core.fleet import RemoteExecutor
    return RemoteExecutor(engine)


_EXECUTORS = {
    "serial": SerialExecutor,
    "thread": ThreadExecutor,
    "process": ProcessExecutor,
    "remote": _remote_executor,
}

# single source of truth: ForgeConfig validates execution_backend against
# config.EXECUTION_BACKENDS, the engine dispatches through _EXECUTORS —
# fail at import if the two ever drift
assert set(_EXECUTORS) == set(EXECUTION_BACKENDS), \
    (sorted(_EXECUTORS), sorted(EXECUTION_BACKENDS))


class OptimizationEngine:
    """Suite-level orchestrator over a shared :class:`ForgePipeline`.

    New code should construct it through the :class:`repro.core.forge.Forge`
    facade (``Forge(ForgeConfig(...))``); the kwarg constructor remains as
    the compatibility shim, and ``config=`` supplies every operational knob
    (workers, execution backend, cache path/size) from one
    :class:`ForgeConfig`."""

    def __init__(self,
                 pipeline: Optional[ForgePipeline] = None,
                 workers: Optional[int] = None,
                 cache: Optional[ResultStore] = None,
                 cache_path: Optional[pathlib.Path] = None,
                 cache_max_entries: Optional[int] = None,
                 backend: Optional[str] = None,
                 config=None,
                 on_result=None,
                 observer=None):
        # explicit kwargs always win; config fills what was left unset
        if config is not None:
            pipeline = pipeline or ForgePipeline.from_config(config)
            workers = config.workers if workers is None else workers
            backend = backend or config.execution_backend
            cache_path = cache_path or config.cache_path
            if cache_max_entries is None:
                cache_max_entries = config.cache_max_entries
        self.pipeline = pipeline or ForgePipeline()
        self.workers = max(1, int(workers if workers is not None else 1))
        self.backend = backend or "thread"
        if self.backend not in EXECUTION_BACKENDS:
            raise ValueError(f"unknown execution backend {self.backend!r}; "
                             f"choose one of {sorted(EXECUTION_BACKENDS)}")
        self.cache = cache or ResultStore(
            cache_path,
            max_entries=(cache_max_entries if cache_max_entries is not None
                         else 512))
        self.stats = EngineStats()
        self.verify_stats = VerifyStats()
        # engine-owned cross-job verification cache: sessions of every job
        # this engine runs (serial/thread) read through and write back; the
        # process backend gives workers private caches plus planner warm
        # slices (see _process_worker_main). None when sharing is disabled.
        cfg = self.pipeline.config
        self.verify_shared: Optional[SharedVerifyCache] = (
            SharedVerifyCache(cfg.shared_verify_cache_bytes)
            if (cfg.shared_verify_cache_bytes > 0
                and cfg.verify_fastpath != "off") else None)
        # observer hook: called with each EngineResult as it completes
        # (serialized under a lock — observers need not be thread-safe)
        self.on_result = on_result
        # unified observer (core.observers.ForgeObserver or any legacy
        # object — as_observer adapts both): receives StageEvent/JobEvent/
        # TransferEvent for every batch this engine runs, serialized under
        # the notify lock
        self.observer = as_observer(observer)
        self._notify_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        # per-key in-flight locks: duplicate jobs submitted in one batch
        # coalesce (first computes, the rest wait and replay) instead of
        # racing N full searches; pruned after every run_batch so the dict
        # doesn't grow without bound across a long-lived driver
        self._inflight: Dict[str, threading.Lock] = {}
        self._inflight_lock = threading.Lock()
        self._executors: Dict[str, Any] = {}

    # ------------------------------------------------------------------
    def _get_executor(self, name: Optional[str] = None):
        name = name or self.backend
        if name not in self._executors:
            self._executors[name] = _EXECUTORS[name](self)
        return self._executors[name]

    def close(self):
        """Shut down live executors (the process pool in particular).
        Idempotent; the engine can be reused — the next batch lazily
        rebuilds whatever it needs."""
        executors, self._executors = self._executors, {}
        for ex in executors.values():
            ex.close()

    def __enter__(self) -> "OptimizationEngine":
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------------
    def _keys(self, job: KernelJob) -> tuple:
        """(exact key, family key, ladder, dims) — see
        :func:`compute_job_keys`. Kept as the single-job convenience; batch
        dispatch goes through the executor's ``compute_keys`` so the work
        can run worker-side."""
        return compute_job_keys(self.pipeline, job)

    # ------------------------------------------------------------------
    def submit(self, job: KernelJob) -> EngineResult:
        """Optimize one job (cache-aware). Single-job convenience over
        ``run_batch``."""
        return self.run_batch([job])[0]

    def run_batch(self, jobs: Sequence[KernelJob],
                  on_stage=None, observer=None) -> List[EngineResult]:
        """Optimize a batch. Results come back in submission order.

        ``observer`` is an optional per-batch :class:`~repro.core.observers
        .ForgeObserver` (or any legacy observer object — it is adapted via
        ``as_observer``): it receives every ``StageEvent`` (with the job's
        *submission index*), every ``JobEvent``, and ``TransferEvent``s for
        this batch, serialized under the engine's notify lock alongside the
        engine-level observer. This is the one observer surface new code
        should use.

        ``on_stage`` is the deprecated loose-callback form of the same
        thing, called as ``on_stage(index, job_name, record)`` with the
        job's submission index. It fires on every backend; on the process
        and remote backends the events are the ones streamed back from the
        workers. It is called unserialized (its original contract) — the
        caller owns any locking.

        Determinism: priors are frozen once per batch and transfer seeds
        once per *phase*, so a job's candidate ordering never depends on
        which other jobs happened to finish first — ``workers=1`` and
        ``workers=N`` (on any backend) are result-equivalent. Scheduling is
        two-phase: the first job of each family (the leader) runs in phase 1
        against the pre-batch store; remaining family members run in phase 2
        seeded from a snapshot taken at the phase boundary, so a cold leader
        can seed its in-batch siblings without making results racy."""
        cfg = self.pipeline.config
        priors = (self.pipeline.history.snapshot_priors(cfg.prior_policy)
                  if self.pipeline.warm_start else {})
        observers = [o for o in (self.observer, as_observer(observer))
                     if o is not None]
        stage_cb = self._stage_dispatcher(observers, on_stage)
        notify = self._result_dispatcher(observers)
        executor = self._get_executor()
        try:
            # key computation is dispatched through the executor so it runs
            # worker-side (threads / spawned processes) instead of
            # serializing on the parent before the first job can start
            keys = executor.compute_keys(jobs)
            # batch execution planning: execute each *duplicated* oracle
            # slice once, parent-side, pre-populating the shared cache so
            # every member of the duplicate set starts warm ("oracle-slice
            # leaders" — the family leader/follower idea at verify grain)
            plan = self._plan_batch(jobs)
            leaders: List[int] = []
            followers: List[int] = []
            seen = set()
            for i, k in enumerate(keys):
                # group by the coarsest (rank) tier: any finer-tier match
                # implies a rank match, so every potential in-batch seed
                # relationship crosses the phase boundary
                fam = k[1]
                (followers if fam in seen else leaders).append(i)
                seen.add(fam)
            results: List[Optional[EngineResult]] = [None] * len(jobs)
            for phase in (leaders, followers):
                if not phase:
                    continue
                # per-job graded neighbor snapshot, frozen at the phase
                # boundary (deterministic under any backend/worker count)
                seeds = {i: self.cache.ladder_members(keys[i][2], keys[i][3])
                         for i in phase}
                executor.run_phase(jobs, phase, keys, priors, seeds, results,
                                   plan=plan, on_stage=stage_cb,
                                   notify=notify)
            return results
        finally:
            executor.end_batch()
            self.cache.flush()
            # prune the coalescing locks: every job of this batch has
            # finished, so the entries are dead weight (a concurrent
            # run_batch re-creates any lock it still needs; worst case two
            # overlapping batches duplicate one search, never deadlock)
            with self._inflight_lock:
                self._inflight.clear()

    # ------------------------------------------------------------------
    def _plan_batch(self, jobs: Sequence[KernelJob]) -> Dict[int, list]:
        """Batch-level execution planner. Jobs are grouped by the rename-
        invariant :func:`program_exec_fingerprint` of their ci program; for
        each signature held by two or more jobs the first member's oracle
        prep + initial program execution run once, parent-side, through a
        session wired to the shared cache — every duplicate then replays
        those entries instead of re-executing them. Returns ``{job index:
        [shared-cache keys]}``, the warm slice the process backend ships at
        dispatch (in-process backends read the live cache and ignore it).

        Planning is a pure optimization: it only moves *where* the first
        execution of a slice happens, never its result, and any planner
        failure just leaves the affected jobs starting cold."""
        cfg = self.pipeline.config
        shared = self.verify_shared
        plan: Dict[int, list] = {}
        if shared is None or not cfg.batch_exec_planning:
            return plan
        sigs: Dict[str, List[int]] = {}
        for i, job in enumerate(jobs):
            try:
                sig = program_exec_fingerprint(job.ci_program)
            except Exception:  # noqa: BLE001 — planning must never raise
                continue
            sigs.setdefault(sig, []).append(i)
        for sig, idxs in sigs.items():
            if len(idxs) < 2:
                continue  # a unique slice warms nobody; the job runs it
            rep = jobs[idxs[0]]
            recorder = _RecordingSharedCache(shared)
            session = VerifySession(
                shared=recorder,
                check_shared=(cfg.verify_fastpath == "check"))
            try:
                inputs, params, _ = session.oracle_prep(
                    rep.ci_program.graph, prepare_oracle)
                run_program_cached(rep.ci_program, inputs, params, session,
                                   use_pallas=cfg.use_pallas_exec)
            except Exception:  # noqa: BLE001 — cold start, not a failure
                continue
            with self._stats_lock:
                vs = self.verify_stats
                vs.planner_signatures += 1
                vs.planner_deduped_jobs += len(idxs) - 1
                vs.planner_group_execs += (session.stats.group_misses
                                           - session.stats.shared_group_hits)
                vs.planner_oracle_preps += (
                    session.stats.oracle_misses
                    - session.stats.shared_oracle_hits)
            for i in idxs:
                plan[i] = list(recorder.keys)
        return plan

    # ------------------------------------------------------------------
    def _stage_dispatcher(self, observers, on_stage):
        """One internal ``(index, job_name, record)`` callback carrying both
        observer surfaces: typed observers see a :class:`StageEvent` under
        the notify lock; the deprecated loose ``on_stage`` callback fires
        outside it (its documented contract: the caller owns locking).
        ``None`` when nobody is listening, so backends can skip stage-event
        decode entirely."""
        if not observers and on_stage is None:
            return None

        def dispatch(index, job_name, record):
            if observers:
                event = StageEvent(job_name, record, index=index)
                with self._notify_lock:
                    for obs in observers:
                        obs.on_stage(event)
            if on_stage is not None:
                on_stage(index, job_name, record)
        return dispatch

    def _result_dispatcher(self, observers):
        """The per-batch job-completion dispatcher: legacy ``on_result``
        hook first, then every observer's ``on_job``, then (for transfer-
        seeded results) every observer's ``on_seed_transfer`` — the same
        ordering the old Forge fan-out produced. All under the notify lock
        so observers need not be thread-safe."""
        if not observers and self.on_result is None:
            return None

        def dispatch(eres: EngineResult):
            with self._notify_lock:
                if self.on_result is not None:
                    self.on_result(eres)
                event = JobEvent(eres)
                for obs in observers:
                    obs.on_job(event)
                if eres.transfer:
                    tevent = TransferEvent(eres)
                    for obs in observers:
                        obs.on_seed_transfer(tevent)
        return dispatch

    def _notify_result(self, eres: EngineResult, notify=None):
        """Deliver one completed result: through the batch dispatcher when
        one is active, else straight to the legacy ``on_result`` hook (the
        path for executors driven outside ``run_batch``)."""
        if notify is not None:
            notify(eres)
        elif self.on_result is not None:
            with self._notify_lock:
                self.on_result(eres)

    # ------------------------------------------------------------------
    def _apply_outcome(self, outcome: Mapping[str, Any]):
        """Fold one job's outcome flags into the engine stats (shared by the
        in-process paths and the process backend's parent-side accounting)."""
        with self._stats_lock:
            self.stats.jobs += 1
            if outcome["cache_hit"]:
                self.stats.cache_hits += 1
            else:
                self.stats.cache_misses += 1
                if outcome["had_seed"]:
                    if outcome["transferred"]:
                        self.stats.family_transfers += 1
                    else:
                        self.stats.transfer_fallbacks += 1
            if outcome["replay_fallback"]:
                self.stats.replay_fallbacks += 1
            verify = outcome.get("verify")
            if verify:
                self.verify_stats.add_session(verify)

    # ------------------------------------------------------------------
    def _run_job(self, job: KernelJob, keys: tuple,
                 priors: Mapping[str, int],
                 seed_pairs: Sequence, on_stage=None,
                 notify=None) -> EngineResult:
        exact_key = keys[0]
        with self._inflight_lock:
            job_lock = self._inflight.setdefault(exact_key, threading.Lock())
        with job_lock:
            eres = self._run_job_locked(job, keys, priors, seed_pairs,
                                        on_stage=on_stage)
        self._notify_result(eres, notify)
        return eres

    def _run_job_locked(self, job: KernelJob, keys: tuple,
                        priors: Mapping[str, int],
                        seed_pairs: Sequence, on_stage=None) -> EngineResult:
        exact_key, family_key = keys[0], keys[1]
        entry = self.cache.get(exact_key)
        result, outcome = execute_job(self.pipeline, job, entry,
                                      seed_pairs, exact_key, priors,
                                      shared=self.verify_shared,
                                      on_stage=on_stage)
        if outcome["entry"] is not None:
            self.cache.put(exact_key, outcome["entry"], family=family_key,
                           flush=False, ladder=keys[2], dims=keys[3])
        self._apply_outcome(outcome)
        return EngineResult(job, result, exact_key,
                            cache_hit=outcome["cache_hit"],
                            transfer=outcome["transferred"],
                            seed_steps=result.seed_steps_applied,
                            replay_fallback=outcome["replay_fallback"],
                            had_seed=outcome["had_seed"],
                            verify=outcome.get("verify"))
