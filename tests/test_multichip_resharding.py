"""The dp+ep+sp+tp+pp transformer step must compile WITHOUT XLA's
"Involuntary full rematerialization" fallback (VERDICT r1 item 5): a spec
mismatch around a shard_map makes SPMD replicate a tensor to reshard it —
correct but replicating on real hardware. The dryrun is executed in a
subprocess so the partitioner's C++ log output can be captured."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_transformer_dryrun_has_no_involuntary_resharding():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-3000:]
    assert "dryrun transformer(8)" in out
    assert "Involuntary full rematerialization" not in out, out[-3000:]
