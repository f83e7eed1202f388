"""Pipeline parallelism: staged shard_map execution must equal the serial
layer scan, forward and backward (autodiff through ppermute)."""
import os

import pytest

# this test needs >= 4 local devices; when the suite runs under the normal
# 1-device CPU env we spawn a subprocess with host_platform_device_count=4
_SRC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax import lax
from repro.launch.mesh import auto_mesh
from repro.runtime.pipeline import pipeline_forward

mesh = auto_mesh((4,), ("pod",))
rngk = jax.random.PRNGKey(0)
L, D, B = 8, 16, 12
params = {"w": jax.random.normal(rngk, (L, D, D)) * 0.3,
          "b": jax.random.normal(jax.random.PRNGKey(1), (L, D)) * 0.1}
x = jax.random.normal(jax.random.PRNGKey(2), (B, D))

def layer_fn(lp, h):
    return jnp.tanh(h @ lp["w"] + lp["b"])

def serial(params, x):
    def body(h, lp):
        return layer_fn(lp, h), None
    out, _ = lax.scan(body, x, params)
    return out

with mesh:
    piped = jax.jit(lambda p, x: pipeline_forward(layer_fn, p, x,
                                                  n_microbatches=6))(params, x)
ref = serial(params, x)
err = float(jnp.abs(piped - ref).max())
assert err < 1e-5, f"forward mismatch {err}"

# backward: grads through the pipeline must match serial grads
def loss_p(p, x):
    with mesh:
        return (pipeline_forward(layer_fn, p, x, 6) ** 2).mean()
def loss_s(p, x):
    return (serial(p, x) ** 2).mean()
with mesh:
    gp = jax.jit(jax.grad(loss_p))(params, x)
gs = jax.grad(loss_s)(params, x)
gerr = max(float(jnp.abs(gp[k] - gs[k]).max()) for k in gp)
assert gerr < 1e-5, f"grad mismatch {gerr}"
print("PIPELINE_OK", err, gerr)
"""


def test_pipeline_matches_serial(tmp_path):
    import subprocess
    import sys
    script = tmp_path / "pipe_check.py"
    script.write_text(_SRC)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=600, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PIPELINE_OK" in out.stdout
