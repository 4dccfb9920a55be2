"""chip_smoke.py's contract, as far as a CPU can check it: it refuses to
run without a TPU, a failing leg fails the run without a result line, and
its legs still drive today's entry points at a toy size (~20 s). None of
this says anything about the chip — only a chip run does."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_refuses_a_non_tpu_platform_and_names_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300, cwd=REPO)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "no accelerator" in proc.stderr
    # the device line is printed, the result line is not
    assert "platform cpu" in proc.stdout
    assert '"ok"' not in proc.stdout


def test_a_failing_leg_fails_the_run_without_a_result_line(monkeypatch,
                                                           capsys):
    """No try/except records an error and carries on: a leg that raises
    ends ``main`` by exception (a non-zero exit for the script) before
    the result line is printed."""
    import chip_smoke
    from large_scale_recommendation_tpu.utils import platform

    monkeypatch.setattr(platform, "device_summary", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(platform, "enable_compilation_cache",
                        platform.compilation_cache_dir)
    ran = []

    def failing_run(sizes):
        ran.append(sizes)
        raise AssertionError("chip_smoke check failed: planted")

    monkeypatch.setattr(chip_smoke, "run", failing_run)
    with pytest.raises(AssertionError, match="planted"):
        chip_smoke.main()
    assert ran == [chip_smoke.Sizes()]  # main() runs the flagship sizes
    assert '"ok"' not in capsys.readouterr().out

    # and the same main() prints the contract's last line when legs pass
    monkeypatch.setattr(chip_smoke, "run", lambda sizes: None)
    assert chip_smoke.main() == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_flagship_sizes_are_full_width():
    """Width is not negotiable: the default sizes are the ML-25M shape at
    rank 128 with the bench's geometry."""
    import chip_smoke

    s = chip_smoke.Sizes()
    assert (s.num_users, s.num_items) == (None, None)  # the named shape
    assert (s.nnz, s.rank, s.blocks, s.minibatch) == (
        25_000_095, 128, 8, 32768)
    cfg = chip_smoke.dsgd_config(s)
    assert (cfg.lambda_, cfg.learning_rate, cfg.lr_schedule,
            cfg.init_scale, cfg.minibatch_sort) == (
        0.1, 0.3, "warm_boost", 0.08, "item")


@pytest.mark.parametrize("cell", ["netflix100m-r128",
                                  "netflix100m-r128-ring4"])
def test_fit_cells_train_with_the_smokes_settings(cell):
    """``dsgd_config`` is the one statement of the DSGD settings in the
    tree: the fit cells' config files must say the same."""
    import chip_smoke

    with open(os.path.join(REPO, "benchmark", "configs",
                           f"{cell}.json")) as f:
        conf = json.load(f)
    cfg = chip_smoke.dsgd_config(chip_smoke.Sizes())
    assert {k: conf[k] for k in (
        "lambda", "learning_rate", "lr_schedule", "minibatch_size",
        "minibatch_sort", "collision_mode", "init_scale", "solver_seed",
        "num_blocks")} == {
        "lambda": cfg.lambda_, "learning_rate": cfg.learning_rate,
        "lr_schedule": cfg.lr_schedule,
        "minibatch_size": cfg.minibatch_size,
        "minibatch_sort": cfg.minibatch_sort,
        "collision_mode": cfg.collision_mode,
        "init_scale": cfg.init_scale, "solver_seed": cfg.seed,
        # one chip takes the smoke's blocks; the ring's are its chips
        "num_blocks": cfg.num_blocks if conf["chips"] == 1 else 4,
    }


def test_legs_rehearse_on_cpu_at_toy_size():
    """The legs against today's entry points, on virtual CPU devices at a
    toy size: control flow and checks only."""
    import chip_smoke
    from large_scale_recommendation_tpu.obs.introspect import Introspector

    toy = chip_smoke.Sizes(
        num_users=640, num_items=384, nnz=120_000, rank=16, sweeps=3,
        blocks=2, minibatch=1024, als_nnz=40_000, request_sizes=(1, 7, 40))
    witness = Introspector()
    assert witness.install()
    try:
        chip_smoke.run(toy)
    finally:
        witness.uninstall()
    assert witness.errors == 0 and witness.compile_count
