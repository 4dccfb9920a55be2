"""Shared by the benchmark's tests: toy-size cells built from the real
cells' files (every width and size shrunk; the limits are the real ones)."""

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

FIT_TOY = dict(num_users=2000, num_items=800, nnz=200000, num_factors=16,
               minibatch_size=1024, target_rmse=0.5)
SERVE_TOY = dict(num_users=5000, num_items=4096, num_factors=32)


def toy_cell(name: str, **config_overrides):
    cell = copy.deepcopy(harness.resolve_cell(name))
    if cell.traffic["runner"] == "fit":
        cell.config.update(FIT_TOY)
    else:
        cell.config.update(SERVE_TOY)
        if "offered_users_per_s" in cell.traffic:
            cell.traffic["offered_users_per_s"] = 300.0
    cell.config.update(config_overrides)
    return cell


def run_toy(name: str, seed: int = 5, seconds: float = 2.0,
            trace: bool = False, control=None, **config_overrides):
    """One CPU rehearsal: ``(parsed result line, everything collected)``."""
    import json

    from benchmark.run import run_cell

    cell = toy_cell(name, **config_overrides)
    if cell.traffic["runner"] == "fit":
        seconds = float(cell.traffic["full_at_seconds"])
    line, out = run_cell(cell.name, seed, seconds, trace, require_tpu=False,
                         control=control, cell=cell)
    return json.loads(line), out
