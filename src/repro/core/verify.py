"""Four-level verification cascade (paper §IV-B-c).

``compile_and_verify`` is the single *tool* the CoVeR agent invokes. Each
level gates the next; the first failure returns a structured diagnostic that
becomes the agent's observation for the next refinement iteration:

  1. Syntax      — the candidate program validates and traces abstractly.
  2. Structure   — KB hardware constraints hold (block alignment, VMEM budget,
                   MXU minimums, f32 accumulation, dtype bans, ...); messages
                   carry remediation instructions, paper-style.
  3. Correctness — executed (real Pallas kernels: compiled on a TPU, the
                   interpreter elsewhere) against the seeded oracle
                   outputs; allclose(rtol, atol) + NaN/Inf gates; mismatch
                   diagnostics include max-abs/mean/rel-diff and exceed
                   counts plus likely causes.
  4. Performance — the v5e roofline cost model must beat the incumbent. On
                   failure the agent receives both timings + TFLOPS +
                   alternative-strategy suggestions.

Returns the success sentinel only when all four pass.

With ``verify_fastpath`` enabled, levels 1-4 run against a
:class:`~repro.core.verify_cache.VerifySession` (memoized traces, structure
verdicts, group executions, costs) that may itself read through an
engine-owned cross-job :class:`~repro.core.verify_cache.SharedVerifyCache`.
``"check"`` mode extends its bit-identical contract down that shared layer:
besides cross-checking every report against the uncached cascade, each
shared-cache hit (a group execution or a positionally rebound oracle prep
seeded by *another job*) is byte-compared against a fresh local execution
before adoption, so a corrupt or colliding shared entry raises
:class:`VerifyFastpathDivergence` at the exact artifact that diverged
rather than surfacing as a numeric drift in some later verdict.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.context import ProblemContext
from repro.core.executor import ExecUnsupported, run_program
from repro.core.verify_cache import (VerifyFastpathDivergence, VerifySession,
                                     run_program_cached)
from repro.hw.specs import dtype_itemsize
from repro.ir.cost import CostModel
from repro.ir.schedule import KernelProgram
from repro.kb.loader import KnowledgeBase

SUCCESS = "VERIFIED: correct and faster — all checks passed"

# Acceptance threshold for the performance level: a candidate must beat the
# incumbent by this factor. Shared with the scheduler's cost-ranked early
# stop — a candidate whose roofline estimate can't clear this bar can never
# be accepted, so verifying it is pure waste.
MIN_SPEEDUP = 1.001


@dataclasses.dataclass
class VerifyReport:
    ok: bool
    level: str                   # syntax | structure | correctness | performance | success
    observation: str
    candidate_time: Optional[float] = None
    incumbent_time: Optional[float] = None
    metrics: Dict[str, float] = dataclasses.field(default_factory=dict)
    # cost-first screening skipped the correctness execution: the candidate
    # cannot beat the incumbent, so the expensive oracle comparison is
    # deferred until (and unless) the fallback extractor needs it
    correctness_deferred: bool = False

    @property
    def speedup(self) -> Optional[float]:
        if self.candidate_time and self.incumbent_time:
            return self.incumbent_time / self.candidate_time
        return None


# ----------------------------------------------------------------------
# level 2 checks, keyed by KB constraint check.type
# ----------------------------------------------------------------------

def _check_structure(program: KernelProgram, ctx: ProblemContext,
                     kb: KnowledgeBase) -> List[str]:
    errors: List[str] = []
    sched = program.schedule
    g = program.graph
    sub, lane = ctx.spec.min_tile(sched.compute_dtype)

    # dtype bans
    for c in kb.critical_constraints():
        if c.check.get("type") == "dtype_ban":
            banned = c.check.get("value", "float64")
            offenders = [n.name for n in g.toposorted() if str(n.dtype) == banned]
            if sched.compute_dtype == banned:
                offenders.append(f"schedule.compute_dtype={banned}")
            if offenders:
                errors.append(
                    f"INVALID dtype {banned} at {offenders[:4]}: {c.description.strip()} "
                    f"Fix: {c.correct}")

    for grp in sched.groups:
        cfg = grp.config
        if not grp.impl.startswith("pallas"):
            continue
        if cfg is None:
            errors.append(f"INVALID group {grp.name}: pallas impl without a "
                          f"PallasConfig. Fix: attach a config (hw query defaults).")
            continue
        if cfg.block_m <= 0 or cfg.block_n <= 0 or cfg.block_k <= 0:
            errors.append(f"INVALID blocks in {grp.name}: non-positive block size.")
            continue
        root = g.node(grp.root)
        if grp.impl == "pallas_blockspec":
            if cfg.block_n % lane or cfg.block_m % sub:
                errors.append(
                    f"INVALID block_shape=({cfg.block_m},{cfg.block_n}) in {grp.name}: "
                    f"must be multiples of the native ({sub},{lane}) tile at "
                    f"{sched.compute_dtype}. Valid examples: ({sub},{lane}), "
                    f"({sub*2},{lane}), ({sub*16},{lane*4}).")
            if root.op == "matmul" and cfg.block_k % lane:
                errors.append(
                    f"INVALID block_k={cfg.block_k} in {grp.name}: contraction tile "
                    f"must be a multiple of {lane} (MXU native).")
        # VMEM budget. Note: the naive kernel's whole-operand refs spilling to
        # HBM is a *performance* pathology (the cost model charges it), not a
        # compile failure — real naive Triton/Pallas kernels run, slowly. The
        # hard gate applies to the declared BlockSpec working set, which
        # Mosaic would genuinely refuse to allocate.
        isz = dtype_itemsize(sched.compute_dtype)
        stream = (cfg.block_m * cfg.block_k + cfg.block_k * cfg.block_n) * isz
        acc = cfg.block_m * cfg.block_n * 4
        ws = stream * max(1, cfg.num_stages) + acc
        if ws > ctx.spec.vmem_bytes:
            errors.append(
                f"INVALID VMEM working set {ws >> 20} MiB > budget "
                f"{ctx.spec.vmem_bytes >> 20} MiB in {grp.name}: shrink BLOCK_K "
                f"first, then BLOCK_N; or reduce num_stages.")
        if cfg.num_stages < 1:
            errors.append(f"INVALID num_stages={cfg.num_stages} in {grp.name}: "
                          f"must be >= 1.")
        if cfg.acc_dtype not in ("float32",):
            errors.append(
                f"INVALID acc_dtype={cfg.acc_dtype} in {grp.name}: matmul "
                f"accumulation must be float32 (bf16 acc loses ~3 digits on "
                f"long K). Fix: acc_dtype='float32'.")
        if cfg.persistent and root.op == "matmul":
            sem = tuple(cfg.dimension_semantics)
            if sem and all(s == "parallel" for s in sem):
                errors.append(
                    f"INVALID dimension_semantics={sem} in {grp.name}: a "
                    f"persistent accumulator revisits blocks; the revisiting "
                    f"dim must be 'arbitrary'.")
        if grp.impl == "pallas_naive" and root.op == "matmul" and len(root.shape) == 2:
            m, n_ = root.shape
            a_shape = g.node(root.inputs[0]).shape
            k = a_shape[0] if root.attrs.get("transpose_a") else a_shape[-1]
            if m % cfg.block_m or n_ % cfg.block_n or k % cfg.block_k:
                errors.append(
                    f"INVALID naive kernel in {grp.name}: shape ({m},{n_},{k}) not "
                    f"divisible by blocks ({cfg.block_m},{cfg.block_n},{cfg.block_k}) "
                    f"and the kernel has no boundary checks. Fix: modernize to "
                    f"BlockSpec tiling (auto-masked) or choose dividing blocks.")
    return errors


# ----------------------------------------------------------------------
def _diff_diagnostics(got: jnp.ndarray, want: jnp.ndarray,
                      rtol: float, atol: float) -> str:
    got64 = np.asarray(got, np.float64)
    want64 = np.asarray(want, np.float64)
    adiff = np.abs(got64 - want64)
    denom = np.maximum(np.abs(want64), 1e-12)
    rdiff = adiff / denom
    exceed = adiff > (atol + rtol * np.abs(want64))
    likely = []
    if got64.shape != want64.shape:
        likely.append(f"shape mismatch {got64.shape} vs {want64.shape}")
    if np.isnan(got64).any():
        likely.append("NaNs present (unstable exp/softmax? missing max-subtract?)")
    if exceed.mean() > 0.9:
        likely.append("wholesale mismatch: wrong strides / transposed loads / "
                      "wrong operand order")
    elif exceed.any():
        frac_tail = exceed.reshape(-1)[-max(1, exceed.size // 16):].mean()
        if frac_tail > 4 * exceed.mean():
            likely.append("errors concentrated at the tail: missing boundary "
                          "checks on ragged edges")
        else:
            likely.append("scattered tolerance exceedances: accumulation dtype "
                          "or reassociation too aggressive")
    return (f"max_abs_diff={adiff.max():.3e} mean_diff={adiff.mean():.3e} "
            f"max_rel_diff={rdiff.max():.3e} "
            f"exceed={int(exceed.sum())}/{exceed.size} "
            f"({100.0 * exceed.mean():.2f}%). Likely causes: "
            + ("; ".join(likely) if likely else "minor numeric drift"))


# ----------------------------------------------------------------------
def run_correctness(candidate_ci: KernelProgram,
                    ctx: ProblemContext,
                    use_pallas: bool = True,
                    session: Optional[VerifySession] = None
                    ) -> Optional[VerifyReport]:
    """Level 3 of the cascade: execute the candidate against the seeded
    oracle. Returns ``None`` when every output matches, else the failure
    report. Split out of :func:`compile_and_verify` so the cost-first
    screening path can defer it and the fallback extractor can run it
    lazily."""
    assert ctx.ci_inputs is not None and ctx.oracle_outputs is not None
    try:
        if session is not None:
            got = run_program_cached(candidate_ci, ctx.ci_inputs,
                                     ctx.ci_params or {}, session,
                                     use_pallas=use_pallas)
        else:
            got = run_program(candidate_ci, ctx.ci_inputs,
                              ctx.ci_params or {}, use_pallas=use_pallas)
    except ExecUnsupported as e:
        return VerifyReport(False, "structure",
                            f"NO KERNEL TEMPLATE: {e}. Fix: keep the group "
                            f"as impl='xla' or restructure the fusion.")
    except Exception as e:  # noqa: BLE001
        if session is not None:
            session.stats.exec_errors += 1
        return VerifyReport(False, "correctness",
                            f"RUNTIME ERROR during execution: "
                            f"{type(e).__name__}: {e}")
    want_list = list(ctx.oracle_outputs.items())
    got_list = list(got.items())
    if len(want_list) != len(got_list):
        return VerifyReport(False, "correctness",
                            f"OUTPUT ARITY MISMATCH: candidate produces "
                            f"{len(got_list)} outputs, oracle has {len(want_list)}")
    # rewrites may rename output nodes; outputs are compared positionally
    for (key, want), (gkey, gval) in zip(want_list, got_list):
        gv = np.asarray(gval)
        wv = np.asarray(want)
        if np.isnan(gv).any():
            return VerifyReport(False, "correctness",
                                f"NaN in output {key}: "
                                + _diff_diagnostics(gval, want, ctx.rtol, ctx.atol))
        if np.isinf(gv).any() and not np.isinf(wv).any():
            return VerifyReport(False, "correctness",
                                f"Inf in output {key} where the original has none")
        if gv.shape != wv.shape:
            return VerifyReport(False, "correctness",
                                f"SHAPE MISMATCH on {key}: {gv.shape} vs {wv.shape}")
        if not np.allclose(gv, wv, rtol=ctx.rtol, atol=ctx.atol):
            return VerifyReport(
                False, "correctness",
                f"OUTPUT MISMATCH on {key} (rtol={ctx.rtol}, atol={ctx.atol}): "
                + _diff_diagnostics(gval, want, ctx.rtol, ctx.atol))
    if session is not None:
        session.stats.exec_passed += 1
    return None


def _performance_report(cand, incumbent_time: float,
                        deferred: bool = False) -> VerifyReport:
    t = cand.total_s
    dominant = cand.dominant
    return VerifyReport(
        False, "performance",
        f"SLOWER: candidate {t*1e6:.2f}us vs incumbent "
        f"{incumbent_time*1e6:.2f}us ({incumbent_time/t:.2f}x). "
        f"Candidate achieves {cand.tflops_effective:.1f} effective TFLOPS; "
        f"dominant term: {dominant}. Suggestions: "
        f"{'reduce HBM traffic (bigger tiles, swizzle, fusion)' if 'memory' in dominant else 'raise MXU utilization (aligned >=128 tiles, bf16, pipelining)'}"
        f"; or try a different stage ordering.",
        candidate_time=t, incumbent_time=incumbent_time,
        metrics={"tflops": cand.tflops_effective},
        correctness_deferred=deferred)


def compile_and_verify(candidate_ci: KernelProgram,
                       candidate_bench: KernelProgram,
                       incumbent_time: float,
                       ctx: ProblemContext,
                       kb: KnowledgeBase,
                       cost_model: Optional[CostModel] = None,
                       min_speedup: float = MIN_SPEEDUP,
                       use_pallas: bool = True,
                       session: Optional[VerifySession] = None,
                       cost_first: bool = False) -> VerifyReport:
    """The verification cascade. ``session`` (optional) memoizes traces,
    group executions, structure checks and cost-model results across
    candidates; ``cost_first`` runs the cheap roofline check *before* the
    expensive correctness execution and defers correctness for candidates
    that cannot beat the incumbent (the report carries
    ``correctness_deferred=True``; the CoVeR fallback extractor runs it
    lazily). With both off this is the uncached reference path."""
    cost_model = cost_model or CostModel(ctx.spec)

    # -- level 1: syntax ------------------------------------------------
    try:
        candidate_ci.validate()
        candidate_bench.validate()
        if session is None or not session.trace_known_good(candidate_ci):
            in_structs = {n.name: jax.ShapeDtypeStruct(n.shape, jnp.dtype(n.dtype))
                          for n in candidate_ci.graph.inputs()}
            param_structs = {n.name: jax.ShapeDtypeStruct(n.shape, jnp.dtype(n.dtype))
                             for n in candidate_ci.graph.params()}
            jax.eval_shape(lambda i, p: run_program(candidate_ci, i, p,
                                                    use_pallas=False),
                           in_structs, param_structs)
            if session is not None:
                session.record_trace_ok(candidate_ci)
    except Exception as e:  # noqa: BLE001 — any trace failure is the diagnostic
        return VerifyReport(False, "syntax",
                            f"SYNTAX/TRACE ERROR: {type(e).__name__}: {e}")

    # -- level 2: structure ----------------------------------------------
    if session is not None:
        errors = session.structure_errors(candidate_bench, ctx, kb,
                                          _check_structure)
    else:
        errors = _check_structure(candidate_bench, ctx, kb)
    if errors:
        return VerifyReport(False, "structure", " | ".join(errors))

    # -- levels 3+4: correctness and performance --------------------------
    # The roofline result is needed either way; with ``cost_first`` it runs
    # ahead of correctness so a candidate that cannot beat the incumbent
    # skips the oracle execution entirely.
    if session is not None:
        cand = session.program_cost(cost_model, candidate_bench)
    else:
        cand = cost_model.program_cost(candidate_bench)
    slower = cand.total_s * min_speedup >= incumbent_time

    if cost_first and slower:
        if session is not None:
            session.stats.screened += 1
        return _performance_report(cand, incumbent_time, deferred=True)

    failure = run_correctness(candidate_ci, ctx, use_pallas=use_pallas,
                              session=session)
    if failure is not None:
        return failure

    if slower:
        return _performance_report(cand, incumbent_time)
    t = cand.total_s
    return VerifyReport(True, "success",
                        SUCCESS + f" ({incumbent_time/t:.2f}x, "
                        f"{cand.tflops_effective:.1f} eff-TFLOPS)",
                        candidate_time=t, incumbent_time=incumbent_time,
                        metrics={"tflops": cand.tflops_effective})


# ----------------------------------------------------------------------
def verify_candidate(candidate_ci: KernelProgram,
                     candidate_bench: KernelProgram,
                     incumbent_time: float,
                     ctx: ProblemContext,
                     kb: KnowledgeBase,
                     cost_model: Optional[CostModel] = None,
                     min_speedup: float = MIN_SPEEDUP,
                     use_pallas: bool = True,
                     session: Optional[VerifySession] = None,
                     fastpath: str = "off") -> VerifyReport:
    """Mode dispatcher over :func:`compile_and_verify`:

    * ``"off"`` (or no session) — the uncached reference cascade.
    * ``"on"`` — memoized fast path + cost-first screening. Known caveat:
      for a candidate that is *both* slower than the incumbent and
      incorrect, the trajectory observation is the performance message
      instead of the correctness one (the screen fires first). Accepted
      transforms, ``StageResult``/``TransformLog`` outcomes and fallback
      selection are unaffected (the in-tree proposers only branch on
      structure-level text, which screening never touches), but a custom
      proposer keying on correctness-failure text would see the
      performance message under screening.
    * ``"check"`` — memoized fast path with every level run, cross-checked
      bit-identical against the uncached cascade, **and** the cost-first
      screening decision the ``"on"`` mode would take is validated: a
      deferred report must hide nothing (its lazily-executed correctness
      must agree with the reference level), an undeferred one must equal
      the reference outright. Raises :class:`VerifyFastpathDivergence` on
      any mismatch.
    """
    if fastpath == "off" or session is None:
        return compile_and_verify(candidate_ci, candidate_bench,
                                  incumbent_time, ctx, kb, cost_model,
                                  min_speedup, use_pallas)
    if fastpath == "check":
        fast = compile_and_verify(candidate_ci, candidate_bench,
                                  incumbent_time, ctx, kb, cost_model,
                                  min_speedup, use_pallas, session=session)
        ref = compile_and_verify(candidate_ci, candidate_bench,
                                 incumbent_time, ctx, kb, cost_model,
                                 min_speedup, use_pallas)
        if fast != ref:
            raise VerifyFastpathDivergence(
                f"verify fast path diverged from the uncached cascade for "
                f"{ctx.name}:\n  fast: {fast}\n  ref:  {ref}")
        # cross-check the screening path too (cheap: the session is hot),
        # so "check" exercises everything "on" would actually run
        screened = compile_and_verify(candidate_ci, candidate_bench,
                                      incumbent_time, ctx, kb, cost_model,
                                      min_speedup, use_pallas,
                                      session=session, cost_first=True)
        if screened.correctness_deferred:
            failure = run_correctness(candidate_ci, ctx,
                                      use_pallas=use_pallas, session=session)
            consistent = (
                failure == ref if failure is not None
                else (ref.level == "performance" and dataclasses.replace(
                    screened, correctness_deferred=False) == ref))
            if not consistent:
                raise VerifyFastpathDivergence(
                    f"cost-first screening hid a divergent outcome for "
                    f"{ctx.name}:\n  screened: {screened}\n"
                    f"  deferred correctness: {failure}\n  ref: {ref}")
        elif screened != ref:
            raise VerifyFastpathDivergence(
                f"cost-first path diverged from the uncached cascade for "
                f"{ctx.name}:\n  screened: {screened}\n  ref:  {ref}")
        return ref
    return compile_and_verify(candidate_ci, candidate_bench, incumbent_time,
                              ctx, kb, cost_model, min_speedup, use_pallas,
                              session=session, cost_first=True)
