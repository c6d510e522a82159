"""Typed pipeline/engine configuration with a derived cache signature.

Before this module every layer took its own long kwarg list
(``ForgePipeline``/``StageScheduler``/``OptimizationEngine``) and the result
-store cache key depended on a *hand-maintained* signature string in
``pipeline.py`` — a newly added knob that someone forgot to append would
silently poison the cache (results computed under one policy replayed under
another). :class:`ForgeConfig` fixes both:

* one frozen, picklable dataclass carries every knob — the facade, the
  pipeline, the scheduler and the engine all read from it, and because it
  pickles cleanly it is the job/config codec the ROADMAP's process-pool
  follow-up needs;
* :meth:`ForgeConfig.policy_signature` is **derived from the dataclass
  fields**: every field participates unless it is explicitly declared
  operational via ``metadata={"policy": False}``. Adding a knob therefore
  invalidates stale cache entries *by default*; exclusion is a reviewed,
  visible decision, not an omission.

Operational fields (worker count, cache location/size, dump dir) are the
only exclusions: the engine guarantees ``workers=1`` and ``workers=N`` are
result-equivalent, and where a cache lives on disk cannot change what the
pipeline would produce.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

__all__ = ["ForgeConfig", "EXECUTION_BACKENDS", "VERIFY_FASTPATH_MODES",
           "PRIOR_POLICIES", "POLICY_SIGNATURE_VERSION"]

# where the engine runs jobs; validated here so a typo'd backend fails at
# config construction, not deep inside a batch
EXECUTION_BACKENDS = ("serial", "thread", "process", "remote")

# how the verifier runs: "off" = the uncached reference cascade, "on" =
# memoized incremental verify + cost-first screening, "check" = memoized and
# cross-checked bit-identical against the uncached path on every report
# (raises on divergence — the fast path's executable contract)
VERIFY_FASTPATH_MODES = ("off", "on", "check")

# how candidate-ordering priors are mined from History: "counts" is the
# legacy flat success-count ordering (bit-exact compatibility mode), "mined"
# uses per-(stage, pattern) statistics + cost-model ranking. Single source
# of truth lives next to the mining code.
from repro.core.history import PRIOR_POLICIES  # noqa: E402

# bumped when the signature *format* changes (field encoding, separator…);
# participates in the signature so format changes can never alias old keys
POLICY_SIGNATURE_VERSION = 1


def _operational(**kw):
    """An operational (non-policy) field: excluded from the cache signature
    because it cannot change what the pipeline produces for a job."""
    return dataclasses.field(metadata={"policy": False}, **kw)


def _search_policy(**kw):
    """A policy field that shapes *search order only*: it participates in
    the exact-result cache signature (a changed ordering can change which
    candidate a fresh search accepts first), but is excluded from the
    *transfer* signature — a transferred TransformLog is re-verified step
    by step at the receiving job, so search-order knobs can never make a
    transferred result wrong, and excluding them keeps family keys (and
    ladder keys) byte-compatible with stores written before the knob
    existed."""
    return dataclasses.field(metadata={"policy": True, "transfer": False},
                             **kw)


def _canon(value) -> str:
    """Canonical, process-stable text form of a field value."""
    if value is None:
        return "*"
    if isinstance(value, bool):
        return "1" if value else "0"
    if isinstance(value, (tuple, list)):
        return ",".join(sorted(str(v) for v in value))
    if isinstance(value, float):
        return repr(value)            # round-trippable, no locale
    return str(value)


@dataclasses.dataclass(frozen=True)
class ForgeConfig:
    """Every knob of the Forge pipeline + fleet engine, in one immutable
    value object.

    Policy fields (all participate in :meth:`policy_signature`):

    * ``spec_name`` — hardware generation (resolved via
      ``repro.hw.specs.get_spec``).
    * ``max_iterations`` — CoVeR iterations per stage (paper's T).
    * ``best_of_k`` — independent pipeline passes, best result kept.
    * ``use_pallas_exec`` — execute Pallas lowerings during verification.
    * ``use_planner`` — dependency-constrained planner vs fixed default
      order (ablation hook).
    * ``warm_start`` — history-driven proposer priors.
    * ``stages_enabled`` — ablation subset (``None`` = all registered
      stages); validated against the stage registry.
    * ``use_llm`` — an LLM client participates in planning/proposals.

    Operational fields (excluded — see module docstring): ``workers``,
    ``execution_backend``, ``cache_path``, ``cache_max_entries``,
    ``dump_dir``, ``verify_fastpath``, ``shared_verify_cache_bytes``,
    ``batch_exec_planning``. ``verify_fastpath`` selects the
    memoized incremental-verification path (``repro.core.verify_cache``),
    which is result-equivalent by contract (its ``"check"`` mode asserts
    bit-identical reports against the uncached cascade), so like the
    backend it stays out of the signature. ``execution_backend`` selects
    *where* jobs run
    (``serial`` in-order on the calling thread, ``thread`` across a bounded
    thread pool, ``process`` across spawned worker processes); the engine
    guarantees all three are result-equivalent, so like ``workers`` it can
    never change what the pipeline produces and stays out of the signature.
    """

    spec_name: str = "tpu_v5e"
    max_iterations: int = 5
    best_of_k: int = 1
    use_pallas_exec: bool = True
    use_planner: bool = True
    warm_start: bool = True
    stages_enabled: Optional[Tuple[str, ...]] = None
    use_llm: bool = False

    # learned-search knobs (policy for the exact cache, excluded from the
    # transfer signature — see _search_policy): how priors are mined from
    # History, and whether stage candidate lists are cost-ranked before the
    # first verification (with early stop once the residual candidates are
    # roofline-dominated)
    prior_policy: str = _search_policy(default="mined")
    cost_rank_proposals: bool = _search_policy(default=True)

    workers: int = _operational(default=1)
    execution_backend: str = _operational(default="thread")
    cache_path: Optional[str] = _operational(default=None)
    cache_max_entries: int = _operational(default=512)
    dump_dir: Optional[str] = _operational(default=None)
    # operational like execution_backend: the fast path is result-equivalent
    # by contract (the "check" mode and the throughput benchmark enforce it),
    # so it can never change what the pipeline produces and stays out of the
    # cache signature — stores built either way replay interchangeably
    verify_fastpath: str = _operational(default="on")
    # byte budget of the engine-owned cross-job SharedVerifyCache (group
    # executions + oracle preps, LRU by bytes); 0 disables sharing. Shared
    # entries are content-addressed, so serving one can never change what a
    # job produces — operational, like verify_fastpath
    shared_verify_cache_bytes: int = _operational(default=64 * 1024 * 1024)
    # pre-execute each duplicated oracle slice once per batch, warming the
    # shared cache before dispatch ("oracle-slice leaders"); planning only
    # reorders *where* an execution happens, never its result
    batch_exec_planning: bool = _operational(default=True)

    # -- distributed fleet knobs (execution_backend="remote") ----------
    # All operational: they shape where and how the fleet runs, never what
    # a job produces (the remote backend is result-equivalent by the same
    # contract as thread/process — gated by scripts/backend_equivalence.py).
    # "host:port" the FleetCoordinator binds for worker connections; None
    # binds 127.0.0.1 on an ephemeral port (loopback fleet). Port 0 asks
    # the OS for a free port; read the resolved one off the coordinator.
    fleet_address: Optional[str] = _operational(default=None)
    # local `forge-worker` processes the coordinator spawns against its own
    # address: None spawns `workers` of them (self-contained loopback
    # fleet), 0 spawns none (external workers connect on their own — the
    # multi-host topology), N spawns exactly N alongside any external ones
    fleet_spawn_workers: Optional[int] = _operational(default=None)
    # how long dispatch waits for the first worker to join before failing
    fleet_connect_timeout_s: float = _operational(default=60.0)
    # coordinator ping cadence; a worker silent for fleet_heartbeat_timeout_s
    # is declared lost and its in-flight job is re-dispatched
    fleet_heartbeat_s: float = _operational(default=2.0)
    fleet_heartbeat_timeout_s: float = _operational(default=10.0)
    # auto-respawn budget for coordinator-spawned workers: after a spawned
    # worker is declared lost, the coordinator relaunches a replacement
    # (capped deterministic backoff) up to this many times across the
    # coordinator's lifetime; 0 disables respawning. Externally launched
    # workers are never respawned — their lifecycle isn't ours.
    fleet_max_respawns: int = _operational(default=3)
    # crash-safe coordinator journal: dispatched task ids and merge-once
    # completions are logged here so a coordinator restart re-dispatches
    # the last wave's unfinished tasks instead of forgetting them; None
    # disables journaling (purely in-memory fleet, the pre-PR-10 behavior)
    fleet_journal_path: Optional[str] = _operational(default=None)
    # deterministic fault injection: a repro.core.faults.FaultPlan in its
    # to_json() form, threaded to the coordinator and its spawned workers
    # (chaos gate / fleet tests only; None = no faults). A JSON string
    # rather than a dict so the frozen config stays hashable; validated
    # by parsing in __post_init__.
    fault_spec: Optional[str] = _operational(default=None)

    def __post_init__(self):
        if self.max_iterations < 1:
            raise ValueError("max_iterations must be >= 1")
        if self.execution_backend not in EXECUTION_BACKENDS:
            raise ValueError(
                f"unknown execution_backend {self.execution_backend!r}; "
                f"choose one of {sorted(EXECUTION_BACKENDS)}")
        if self.execution_backend in ("process", "remote"):
            import jax
            if jax.default_backend() == "tpu":
                raise ValueError(
                    f"execution_backend={self.execution_backend!r} spawns "
                    f"JAX worker processes, but a TPU belongs to one process "
                    f"at a time and this one holds it; use 'serial' or "
                    f"'thread' on a TPU")
        if self.verify_fastpath not in VERIFY_FASTPATH_MODES:
            raise ValueError(
                f"unknown verify_fastpath {self.verify_fastpath!r}; "
                f"choose one of {list(VERIFY_FASTPATH_MODES)}")
        if self.prior_policy not in PRIOR_POLICIES:
            raise ValueError(
                f"unknown prior_policy {self.prior_policy!r}; "
                f"choose one of {list(PRIOR_POLICIES)}")
        if self.best_of_k < 1:
            raise ValueError("best_of_k must be >= 1")
        if self.workers < 1:
            raise ValueError("workers must be >= 1")
        if self.cache_max_entries < 1:
            raise ValueError("cache_max_entries must be >= 1")
        if self.shared_verify_cache_bytes < 0:
            raise ValueError("shared_verify_cache_bytes must be >= 0 "
                             "(0 disables cross-job sharing)")
        if self.fleet_spawn_workers is not None and self.fleet_spawn_workers < 0:
            raise ValueError("fleet_spawn_workers must be >= 0 "
                             "(None spawns `workers` loopback processes)")
        if self.fleet_connect_timeout_s <= 0:
            raise ValueError("fleet_connect_timeout_s must be > 0")
        if self.fleet_heartbeat_s <= 0:
            raise ValueError("fleet_heartbeat_s must be > 0")
        if self.fleet_heartbeat_timeout_s < self.fleet_heartbeat_s:
            raise ValueError("fleet_heartbeat_timeout_s must be >= "
                             "fleet_heartbeat_s")
        if self.fleet_max_respawns < 0:
            raise ValueError("fleet_max_respawns must be >= 0 "
                             "(0 disables worker auto-respawn)")
        if self.fleet_journal_path is not None:
            object.__setattr__(self, "fleet_journal_path",
                               str(self.fleet_journal_path))
        if self.fault_spec is not None:
            from repro.core.faults import FaultPlan
            FaultPlan.from_json(self.fault_spec)  # fail fast on bad specs
        if self.fleet_address is not None:
            object.__setattr__(self, "fleet_address", str(self.fleet_address))
            from repro.core.remote import parse_address
            parse_address(self.fleet_address)  # fail fast on a bad address
        if self.stages_enabled is not None:
            # normalize list -> tuple so the config stays hashable/picklable
            object.__setattr__(self, "stages_enabled",
                               tuple(self.stages_enabled))
            from repro.core.stages import DEFAULT_REGISTRY
            for s in self.stages_enabled:
                if s not in DEFAULT_REGISTRY:
                    raise ValueError(
                        f"stages_enabled names unknown stage {s!r}; "
                        f"registered: {list(DEFAULT_REGISTRY.names())}")
        if self.cache_path is not None:
            object.__setattr__(self, "cache_path", str(self.cache_path))
        if self.dump_dir is not None:
            object.__setattr__(self, "dump_dir", str(self.dump_dir))

    # ------------------------------------------------------------------
    @classmethod
    def policy_fields(cls) -> List[dataclasses.Field]:
        """The fields that participate in the cache signature (everything
        not explicitly marked ``metadata={"policy": False}``)."""
        return [f for f in dataclasses.fields(cls)
                if f.metadata.get("policy", True)]

    @classmethod
    def operational_fields(cls) -> List[dataclasses.Field]:
        return [f for f in dataclasses.fields(cls)
                if not f.metadata.get("policy", True)]

    def policy_signature(self) -> str:
        """Stable signature of every policy knob, derived from the dataclass
        fields themselves. Sorted by field name so source-order refactors
        don't shuffle cache keys; versioned so format changes can't alias."""
        parts = [f"{f.name}={_canon(getattr(self, f.name))}"
                 for f in sorted(self.policy_fields(), key=lambda f: f.name)]
        return f"forge-v{POLICY_SIGNATURE_VERSION};" + ";".join(parts)

    @classmethod
    def transfer_fields(cls) -> List[dataclasses.Field]:
        """Policy fields that also scope *transfer* (family/ladder) keys —
        everything policy except search-order knobs marked
        ``metadata={"transfer": False}``."""
        return [f for f in cls.policy_fields()
                if f.metadata.get("transfer", True)]

    def transfer_policy_signature(self) -> str:
        """Signature for family/ladder (transfer) keys. Search-order knobs
        are excluded: transferred logs are re-verified step by step, so
        ordering policy can't invalidate a neighbor's trajectory — and for
        the default search knobs this string is byte-identical to the full
        pre-knob signature, keeping stores written before this PR
        transferable."""
        parts = [f"{f.name}={_canon(getattr(self, f.name))}"
                 for f in sorted(self.transfer_fields(),
                                 key=lambda f: f.name)]
        return f"forge-v{POLICY_SIGNATURE_VERSION};" + ";".join(parts)

    # ------------------------------------------------------------------
    def replace(self, **changes) -> "ForgeConfig":
        return dataclasses.replace(self, **changes)

    def to_dict(self) -> dict:
        """Plain-dict codec (JSON-safe) for process-pool job submission."""
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "ForgeConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown ForgeConfig fields: {sorted(unknown)}")
        return cls(**d)

    # ------------------------------------------------------------------
    def spec(self):
        """Resolve ``spec_name`` to its :class:`repro.hw.specs.TPUSpec`."""
        from repro.hw.specs import get_spec
        return get_spec(self.spec_name)
