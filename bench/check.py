"""Is what the window served correct?

Compared against ``reference.py`` once the window has closed, on the
frames and answers the timed path produced:

* ``keep_mismatch``: micro-batches whose number of frames kept by the
  prefix differs from the reference Skip (exact: limit 0);
* ``preprocess_max_abs``: largest |program - reference| over every
  transformed frame the prefix handed to the extract;
* ``extract_logit_gap``: over a sample drawn from the seed of the frames
  the extract served (every variant and frame shape in it, the longest
  sequences included) and every head the extract answers, the widest
  amount by which a served answer's reference logit lies below the
  reference's best;
* ``tail_mismatch``: queries whose records or window results differ from
  the reference tails applied to the served answers (exact: limit 0);
* ``unanswered``: (micro-batch, query) pairs that never reached the
  query's sink (exact: limit 0).
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

import reference


def _served_rows(fleet, cfg, feeds) -> Tuple[Dict[str, Any], int]:
    """Per feed and group: the kept frame indices and, per row, the
    transformed frame and the served answers; plus keep mismatches."""
    by_feed: Dict[str, List[Any]] = {}
    for feed, req in fleet.server.requests:
        by_feed.setdefault(feed, []).append(req)
    out: Dict[str, Any] = {}
    mismatch = 0
    for f in feeds:
        src = fleet.sources[f.name]
        keep = f.ref_keep
        groups = list(fleet.runtime.forests[f.name].groups())
        reqs = by_feed.get(f.name, [])
        want = []
        for _, first, n in src.pulls:
            kk = np.nonzero(keep[first:first + n])[0] + first
            if len(kk):
                want.append(kk)
        got = [reqs[i::len(groups)] for i in range(len(groups))]
        rows = []
        for gi, g in enumerate(groups):
            rq = got[gi]
            bad = sum(1 for a, b in zip(want, rq) if len(a) != b.n)
            bad += abs(len(want) - len(rq))
            mismatch += bad
            rows.append({"queries": list(g.execution.queries),
                         "variant": rq[0].variant if rq else None,
                         "idx": want, "reqs": rq,
                         "ok": bad == 0})
        out[f.name] = rows
    return out, mismatch


def _preprocess_gap(cfg, feeds, served, control: bool = False) -> float:
    """Largest |program - reference| over the transformed frames; with
    ``control``, the reference's preprocess computed in bfloat16 stands in
    the program's place."""
    worst = 0.0
    for f in feeds:
        pre = [op for op in cfg["prefix"][f.stream]
               if op["op"] == "fused_preprocess"][0]
        for g in served[f.name]:
            if not g["ok"]:
                continue
            for kk, req in zip(g["idx"], g["reqs"]):
                ref = reference.preprocess(f.frames[kk], pre["crop"],
                                           pre["factor"])
                got = reference.preprocess_lower(
                    f.frames[kk], pre["crop"], pre["factor"]) \
                    if control else req.frames
                worst = max(worst, float(np.abs(got - ref).max()))
    return worst


def _tails(cfg, feeds, served, run_result) -> int:
    bad = 0
    for f in feeds:
        per_q = run_result.feeds[f.name].per_query
        for g in served[f.name]:
            if not g["ok"]:
                bad += len(g["queries"])
                continue
            idx = np.concatenate(g["idx"]) if g["idx"] else \
                np.zeros(0, np.int64)
            answers = {}
            if g["reqs"]:
                res = [r.result for r in g["reqs"]]
                answers = {k: np.concatenate([r[k] for r in res])
                           for k in res[0]}
            for qid in g["queries"]:
                q = cfg["queries"][qid]
                sel = np.ones(len(idx), bool)
                window = None
                for op in q["tail"]:
                    if op["op"] == "filter":
                        sel &= reference.predicate(op["pred"], answers,
                                                   len(idx)) \
                            if len(idx) else sel
                    else:
                        window = op
                recs = [dict({"idx": int(idx[i])},
                             **{k: np.asarray(v[i]).tolist()
                                for k, v in answers.items()})
                        for i in np.nonzero(sel)[0]]
                got = per_q[qid]
                wins = reference.tumbling(window["kind"], window["size"],
                                          recs) if window else []
                if got.outputs != recs or \
                        _norm_windows(got.window_results) != wins:
                    bad += 1
    return bad


def _norm_windows(ws):
    return [dict(w, window=tuple(w["window"])) for w in ws]


def sample_rows(served, n_rows: int, seed_words: List[int]):
    """Rows drawn from the seed, spread over (variant, frame shape)."""
    strata: Dict[Tuple, List[Tuple]] = {}
    for feed, groups in served.items():
        for gi, g in enumerate(groups):
            if not g["ok"]:
                continue
            for ri, req in enumerate(g["reqs"]):
                key = (g["variant"],) + tuple(req.frames.shape[1:])
                for row in range(req.n):
                    strata.setdefault(key, []).append((feed, gi, ri, row))
    rng = np.random.default_rng(seed_words + [7])
    per = max(1, n_rows // max(len(strata), 1))
    picked = {}
    for key in sorted(strata, key=str):
        rows = strata[key]
        take = rng.choice(len(rows), size=min(per, len(rows)),
                          replace=False)
        picked[key] = [rows[i] for i in sorted(take)]
    return picked


def extract_gap(served, picked, refs, params, ref_input, control=None):
    """Widest gap of the served answers over every head the extract
    answers (one forward computes them all), and of the control's answers
    where ``control(variant, rows)`` gives them (``None`` otherwise).  The
    control reads the rows the program's extract read; the reference
    reads its own preprocessing of the raw frame, ``ref_input(feed,
    idx)``."""
    gap, gap_control = 0.0, 0.0
    for key, rows in picked.items():
        variant = key[0]
        frames, inputs, answers = [], [], {}
        for feed, gi, ri, row in rows:
            g = served[feed][gi]
            req = g["reqs"][ri]
            frames.append(ref_input(feed, int(g["idx"][ri][row])))
            inputs.append(req.frames[row])
            for k, v in req.result.items():
                answers.setdefault(k, []).append(v[row])
        answers = {k: np.stack(v) for k, v in answers.items()}
        heads = sorted(answers)
        lg = refs[variant].logits(params[variant], np.stack(frames))
        gap = max(gap, reference.widest_gap(lg, answers, heads))
        if control is not None:
            picks = control(variant, np.stack(inputs))
            gap_control = max(gap_control,
                              reference.widest_gap(lg, picks, heads))
    return gap, (gap_control if control is not None else None)


def ref_input_fn(cfg, feeds):
    by_name = {f.name: f for f in feeds}

    def ref_input(feed: str, idx: int) -> np.ndarray:
        f = by_name[feed]
        pre = [op for op in cfg["prefix"][f.stream]
               if op["op"] == "fused_preprocess"][0]
        return reference.preprocess(f.frames[idx:idx + 1], pre["crop"],
                                    pre["factor"])[0]
    return ref_input


def compare(fleet, cfg, feeds, run_result, window_unanswered, model,
            params, seed_words, control=None):
    """The compared numbers of the program, and, where ``control`` (the
    extract's lower-precision path, see ``extract_gap``) is given, the
    same numbers with the control in the program's place: its answers on
    the sampled rows and its preprocess on every transformed frame.  The
    extract's reference is the configuration's own, ``model.reference``
    (``layout.model``).  Returns ``(program, control_or_None)``."""
    refs = {v: model.reference(spec, cfg["patch"])
            for v, spec in cfg["backbone"].items()}
    served, keep_bad = _served_rows(fleet, cfg, feeds)
    picked = sample_rows(served, int(cfg["check"]["rows"]), seed_words)
    gap, gap_control = extract_gap(served, picked, refs, params,
                                   ref_input_fn(cfg, feeds), control)
    program = {
        "keep_mismatch": float(keep_bad),
        "preprocess_max_abs": _preprocess_gap(cfg, feeds, served),
        "extract_logit_gap": gap,
        "tail_mismatch": float(_tails(cfg, feeds, served, run_result)),
        "unanswered": float(window_unanswered),
    }
    if control is None:
        return program, None
    return program, dict(program, extract_logit_gap=gap_control,
                         preprocess_max_abs=_preprocess_gap(
                             cfg, feeds, served, control=True))
