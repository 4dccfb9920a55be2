"""Shared by the benchmark's tests: toy-size cells built from the real
cells' files (every width and size shrunk to the configuration file's own
``toy`` object; the limits are the real ones)."""

import copy
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def toy_cell(name: str, root: str = ROOT, **config_overrides):
    cell = copy.deepcopy(harness.resolve_cell(name, root=root))
    cell.config.update(cell.config["toy"])
    if "offered_users_per_s" in cell.traffic:
        cell.traffic["offered_users_per_s"] = 300.0
    cell.config.update(config_overrides)
    return cell


def run_toy(name: str, seed: int = 5, seconds: float = 2.0,
            trace: bool = False, control=None, root: str = ROOT,
            **config_overrides):
    """One CPU rehearsal: ``(parsed result line, everything collected)``.
    A traffic mix of a fixed amount of work runs all of it."""
    from benchmark.run import run_cell

    cell = toy_cell(name, root, **config_overrides)
    if "full_at_seconds" in cell.traffic:
        seconds = float(cell.traffic["full_at_seconds"])
    line, out = run_cell(cell.name, seed, seconds, trace, require_tpu=False,
                         control=control, cell=cell)
    return json.loads(line), out


def tree_with(tmp_path, files: dict, manifest_edit) -> str:
    """A copy of the benchmark's files (``BENCHMARK.json``, ``benchmark/``)
    under ``tmp_path`` with ``files`` (path under ``benchmark/`` -> text)
    placed beside the others, as a later PR places them; ``manifest_edit``
    adds that PR's entries to the copy's manifest. Returns the copy's
    root. No file of the tree is touched."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for rel, text in files.items():
        path = os.path.join(root, "benchmark", rel)
        assert not os.path.exists(path), f"{rel} would edit a file"
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text if isinstance(text, str) else json.dumps(text))
    manifest = harness.load_manifest()
    manifest_edit(manifest)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
