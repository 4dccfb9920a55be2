"""Solver ``mesh_dsgd``: ``MeshDSGD.fit_device`` over the cell's chips, one
block per chip. It has no segment hook, so the sweep ends come from a
checkpoint manager of the benchmark's own (``SegmentStamps.save``), which
stamps each one-sweep save and keeps the shards. The configuration's keys,
the control and the counts are ``dsgd``'s: the same algorithm on a ring."""

from __future__ import annotations

from benchmark.runners.solvers.dsgd import (  # noqa: F401
    CONTROLS,
    sizes,
    solver_config,
    sweep_flops,
)


def make_fit(cfg: dict, iterations: int, stamps, chips: int, **overrides):
    import jax

    from large_scale_recommendation_tpu.parallel import MeshDSGD, Partitioner
    from large_scale_recommendation_tpu.parallel.dsgd_mesh import (
        MeshDSGDConfig,
    )

    nu, ni = cfg["num_users"], cfg["num_items"]
    kw = solver_config(cfg, iterations, **overrides)
    if cfg["num_blocks"] != chips:
        raise ValueError("mesh_dsgd: the configuration's num_blocks is "
                         f"{cfg['num_blocks']}, the cell has {chips} "
                         "chips; the ring has one block per chip")
    part = Partitioner(devices=jax.local_devices()[:chips])
    solver = MeshDSGD(MeshDSGDConfig(**kw), partitioner=part)
    return lambda u, i, r: solver.fit_device(
        u, i, r, nu, ni, checkpoint_manager=stamps, checkpoint_every=1)
