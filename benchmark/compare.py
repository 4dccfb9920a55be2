"""The comparison that decides ``correct``. Each number compared is printed
beside its limit; a run is correct when every number is at or under its
limit (and finite). The limits live in the configuration's file under
``limits`` and were set from readings on the chip (PERF.md, section 2)."""

from __future__ import annotations

import math
import sys

import numpy as np


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``compared``: name -> {"value", "limit"}; correct iff all hold. A
    number without a limit in the configuration is an error: nothing is
    compared against a guess."""
    compared = {}
    ok = True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for compared number {name!r} in the "
                           "configuration's 'limits'")
        value = float(value)
        limit = float(limits[name])
        good = math.isfinite(value) and value <= limit
        ok = ok and good
        compared[name] = {"value": value, "limit": limit}
    return ok, compared


def print_compared(compared: dict, correct: bool) -> None:
    """The last lines on standard error: each number beside its limit."""
    for name, c in compared.items():
        print(f"compared {name} {c['value']:.6g} limit {c['limit']:.6g}",
              file=sys.stderr)
    print(f"correct {str(bool(correct)).lower()}", file=sys.stderr,
          flush=True)


# -- fit ---------------------------------------------------------------------


def _leaf_norm(x, mask) -> float:
    """Norm over the masked rows: row sums of squares in float32 on the
    device, their total in float64 on the host, so that the norm's own
    rounding stays far under the gaps it is compared by."""
    import jax.numpy as jnp

    rows = jnp.sum(jnp.where(mask[:, None], x, 0.0) ** 2, axis=1)
    return float(np.sqrt(np.asarray(rows, np.float64).sum()))


def _norm_gap(prog, ref, init, seen) -> float:
    """Worst leaf of |‖prog − init‖ − ‖ref − init‖| over the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    p = [_leaf_norm(a - b, m) for a, b, m in zip(prog, init, seen)]
    r = [_leaf_norm(a - b, m) for a, b, m in zip(ref, init, seen)]
    med = float(np.median(r))
    return max(abs(pi - ri) / max(ri, med, 1e-30) for pi, ri in zip(p, r))


def _diff_norm(prog, ref, init, seen) -> float:
    """Worst leaf of ‖prog − ref‖ / ‖ref − init‖."""
    d = [_leaf_norm(a - b, m) for a, b, m in zip(prog, ref, seen)]
    r = [_leaf_norm(a - b, m) for a, b, m in zip(ref, init, seen)]
    med = float(np.median(r))
    return max(di / max(ri, med, 1e-30) for di, ri in zip(d, r))


def fit_numbers(prog_tables, prog_rmse, ref: dict, ref_rmse) -> dict:
    """``prog_tables``/``ref['sweeps']``: per sweep ``(U_id, V_id)`` in id
    space; ``*_rmse``: holdout RMSE after each of those sweeps. Compared
    over the first ``n = min(3, sweeps)`` sweeps (the reference follows no
    more):

    - ``loss_gap``: worst sweep's |RMSE − reference RMSE| / reference RMSE;
    - ``first_update_gap``: the norm-gap of the first sweep's total update
      (what the optimizer was given, worked out from the state after one
      step of the segment loop);
    - ``update_gap``: the norm-gap of the change after sweep n;
    - ``table_diff``: the norm of the difference after sweep n over the
      norm of the reference's change."""
    n = min(len(prog_tables), len(ref["sweeps"]))
    if n == 0:
        raise ValueError("nothing to compare: no sweep on one side")
    init, seen = ref["init"], ref["seen"]
    loss = max(abs(p - r) / r for p, r in zip(prog_rmse[:n], ref_rmse[:n]))
    return {
        "loss_gap": loss,
        "first_update_gap": _norm_gap(prog_tables[0], ref["sweeps"][0],
                                      init, seen),
        "update_gap": _norm_gap(prog_tables[n - 1], ref["sweeps"][n - 1],
                                init, seen),
        "table_diff": _diff_norm(prog_tables[n - 1], ref["sweeps"][n - 1],
                                 init, seen),
    }


# -- serving -----------------------------------------------------------------


def topk_numbers(served_ids, served_scores, ref_top, ref_at_served,
                 ref_std) -> dict:
    """Per sampled user, in units of that user's score spread over the
    catalog:

    - ``topk_gap``: the widest gap by which the reference's score of a
      served item lies below the reference's score at the same rank of its
      own top-k (0 when the served set is the exact top-k);
    - ``score_err``: the widest |served score − reference score of that
      item|."""
    served_ids = np.asarray(served_ids)
    bad = served_ids < 0
    ref_at = np.where(bad, -np.inf, ref_at_served)
    by_ref = -np.sort(-ref_at, axis=1)
    gap = (ref_top - by_ref) / ref_std[:, None]
    err = np.abs(np.asarray(served_scores) - ref_at_served) / ref_std[:, None]
    err = np.where(bad, np.inf, err)
    return {"topk_gap": float(np.max(gap)), "score_err": float(np.max(err))}
