"""Virtual-mesh evidence past 8 devices (VERDICT r4 #7).

The 8-device conftest mesh cannot catch k-scaling pathologies (pad-ratio
blowup at high k, per-shard minibatch divisibility, high-k layout
memory), so the pod-shaped pass runs in a SUBPROCESS with its own
16-device XLA flag — the same isolation trick the 2-process demo test
uses. ``scripts/pod_dryrun.py`` holds the actual workload (shared with
standalone runs); this test pins its JSON contract.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.mark.slow
class TestPodShapedMesh:
    def test_pod_dryrun_16_devices(self):
        """dryrun_multichip(16) + partitioner rules resolution at 16
        devices + the pod-shaped (10:1 vocab, rank 128, k=16) at-scale
        pass + the 2-process local cluster: green run, bounded pad
        ratio, minibatch divisibility, sub-data-std train risk, and the
        MULTICHIP JSON contract (pad-ratio / layout-bytes / throughput
        fields) the --family multichip regression gate consumes.

        The final stdout line must parse as JSON even with stderr
        merged in (stderr is flushed before the final line), so run
        with 2>&1."""
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "pod_dryrun.py"),
             "16"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=REPO, timeout=1800,
        )
        assert proc.returncode == 0, proc.stdout[-3000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["n_devices"] == 16
        # the script asserts the hard bounds; re-pin the headline ones
        # here so a contract drift in the script cannot silently pass
        assert out["max_pad_ratio"] < 2.0
        assert out["train_rmse_after_4_sweeps"] < out["data_std"]
        # the MULTICHIP trajectory contract: every key the multichip
        # regress family watches, plus the 16-device rules coverage
        from scripts.bench_regress import MULTICHIP_KEYS

        for key in MULTICHIP_KEYS:
            assert key in out, key
        assert out["train_ratings_per_s"] > 0
        assert out["layout_bytes"] > 0
        assert out["partitioner_axes_resolved"] >= 5
        # the 2-process local-cluster pass ran (or skipped loudly)
        two = out["two_process"]
        assert two.get("ok") or two.get("skipped"), two
        if two.get("ok"):
            # the pod-observability half (ISSUE 9): process 0 merged
            # both processes' /metrics+/healthz through obs.fleet over
            # real sockets and the aggregate passed its asserts
            assert two.get("fleet_ok"), two
            # the distributed-tracing half (ISSUE 12): the merged pod
            # trace validated and a sampled record resolved to one
            # assembled trace across the process boundary
            assert two.get("trace_ok"), two
