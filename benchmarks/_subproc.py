"""Run a benchmark callable in a subprocess with N host CPU devices.

jax locks the device count at first init, so multi-shard wall-time
measurements (the paper's speedup curves) re-exec python with
``--xla_force_host_platform_device_count=N`` and return JSON via stdout.
The child runs with ``JAX_PLATFORMS=cpu``: these are host-device-count
studies, and a child never contends for an accelerator its parent holds.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def run_with_devices(n_devices: int, module: str, func: str,
                     kwargs: dict, timeout: int = 1200) -> dict:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count={n_devices}")
        import json, sys
        from {module} import {func}
        out = {func}(**{kwargs!r})
        print("@@RESULT@@" + json.dumps(out))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}/src:{REPO}:" + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    for line in proc.stdout.splitlines():
        if line.startswith("@@RESULT@@"):
            return json.loads(line[len("@@RESULT@@"):])
    raise RuntimeError(
        f"subprocess failed (rc={proc.returncode}):\n"
        f"stdout: {proc.stdout[-2000:]}\nstderr: {proc.stderr[-2000:]}")
