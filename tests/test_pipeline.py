"""Pipeline-parallel schedule correctness (subprocess: forced 4 devices)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bubble_fraction():
    from repro.distributed.pipeline import bubble_fraction
    assert bubble_fraction(4, 12) == pytest.approx(3 / 15)
    assert bubble_fraction(1, 8) == 0.0


@pytest.mark.slow
def test_gpipe_matches_sequential_subprocess():
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, os.path.join(%r, "src"))
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import Mesh
from repro.distributed.pipeline import pipeline_apply
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("pipe",))
key = jax.random.PRNGKey(0)
n_stages, n_mb, d = 4, 8, 16
ws = jax.random.normal(key, (n_stages, d, d)) * 0.3
bs = jax.random.normal(jax.random.fold_in(key, 1), (n_stages, d)) * 0.1
mbs = jax.random.normal(jax.random.fold_in(key, 2), (n_mb, 4, d))

def stage_fn(p, x):
    w, b = p
    return jnp.tanh(x @ w + b)

out = pipeline_apply(stage_fn, (ws, bs), mbs, mesh)

# sequential reference
ref = mbs
for s in range(n_stages):
    ref = jnp.tanh(ref @ ws[s] + bs[s])
err = float(jnp.max(jnp.abs(out - ref)))
assert err < 1e-5, err
print(json.dumps({"ok": True, "err": err}))
""" % REPO
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"]
