"""Run a multi-device benchmark section on the devices this process may use.

A section is a code snippet that builds its mesh from `n_dev` devices (plus
any other names passed in `params`) and leaves its results in a dict named
`out`. On an accelerator it runs in THIS process over every attached device:
the process that imported JAX holds the chip, so a child could not use it.
On the CPU it runs in a child interpreter with `n_dev` forced host devices,
because the device count must be forced before JAX starts. Either way the
result's "devices" entry names the platform and device count it ran on.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import jax

_MARKER = "===MULTIDEVICE_SECTION_JSON==="
_DEVICES = """
import jax as _jax
_devs = _jax.devices()
out["devices"] = {"platform": _devs[0].platform, "kind": _devs[0].device_kind,
                  "count": len(_devs)}
"""


def run_section(code: str, params: dict, *, n_dev: int, timeout: int) -> dict:
    """Run `code` over `n_dev` forced CPU devices, or every attached chip."""
    code = textwrap.dedent(code) + _DEVICES
    if jax.default_backend() != "cpu":
        namespace = dict(params, n_dev=len(jax.devices()))
        exec(compile(code, "<benchmark section>", "exec"), namespace)
        return namespace["out"]
    prelude = "".join(f"{k} = {v!r}\n" for k, v in dict(params, n_dev=n_dev).items())
    epilogue = f"import json\nprint({_MARKER!r})\nprint(json.dumps(out))\n"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n_dev}")
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, "-c", prelude + code + epilogue],
                          capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"benchmark section failed:\n{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.split(_MARKER, 1)[1])
