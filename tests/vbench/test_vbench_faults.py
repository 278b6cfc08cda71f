"""A whole run of the harness on the CPU, with no look for a chip, and
with the timed path broken underneath: each fault that a serving cell can
have must turn ``correct`` false, and a sound run must keep it true.

The faults are planted in the program's jitted forward, where the
answers are produced: half of each micro-batch left out (its rows come
back as zeros), one answer altered, and on a four-device data mesh the
exchange between devices left out (every shard's rows are the first
shard's).  The limit is the committed configuration's of the same mode.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from _vbench_tiny import REPO, closed, config, make_root, on_cpu

# the committed configurations' limits, for the tiny model in each mode
LIMIT = {mode: json.loads((REPO / "vbench" / "configs" / f"{name}.json")
                          .read_text())["correct"]["limits"]["logit_err"]
         for mode, name in (("int8", "deit_t_int8"),
                            ("float", "vit_b16_float"))}


def half_batch(out):
    return out.at[(out.shape[0] + 1) // 2:].set(0.0)


def altered(out):
    import jax.numpy as jnp
    return out.at[0, 0].add(0.5 * jnp.max(jnp.abs(out)))


def exchange_left_out(out):
    import jax.numpy as jnp
    rows = out.shape[0] // 4
    return jnp.tile(out[:rows], (4, 1))


def plant(monkeypatch, fault):
    """Break every forward the harness's server runs with ``fault``."""
    from vbench import harness
    build = harness.build_server

    def broken_build(*a, **k):
        server = build(*a, **k)
        forward_for = server._forward_for

        def broken(*fa, **fk):
            fn = forward_for(*fa, **fk)
            return lambda p, x: fault(fn(p, x))

        server._forward_for = broken
        return server

    monkeypatch.setattr(harness, "build_server", broken_build)


def run(tmp_path, monkeypatch, mode, fault=None, chips=1):
    from vbench import harness
    on_cpu(monkeypatch)
    if fault is not None:
        plant(monkeypatch, fault)
    root = make_root(tmp_path, {"tiny.closed": (
        config(mode, LIMIT[mode], chips=(chips,)), closed(), chips)})
    out = harness.run_cell(root, "tiny.closed", 2 ** 31 + 5, 0.5, False)
    return harness.passed(out.checks), out


@pytest.mark.parametrize("mode", ["float", "int8"])
def test_sound_run_is_correct(tmp_path, monkeypatch, mode):
    ok, out = run(tmp_path, monkeypatch, mode)
    assert ok, out.checks
    assert out.failed == 0 and out.attempted > 0
    assert out.metrics["images_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", [half_batch, altered],
                         ids=["half_batch", "altered_answer"])
@pytest.mark.parametrize("mode", ["float", "int8"])
def test_fault_is_caught(tmp_path, monkeypatch, mode, fault):
    ok, out = run(tmp_path, monkeypatch, mode, fault)
    assert not ok, out.checks
    assert out.checks["logit_err"]["value"] > \
        out.checks["logit_err"]["limit"]


_MESH_RUN = r"""
import pathlib, sys
sys.path.insert(0, {tests!r})
import pytest
import test_vbench_faults as t
mp = pytest.MonkeyPatch()
fault = t.exchange_left_out if sys.argv[1] == "fault" else None
ok, out = t.run(pathlib.Path(sys.argv[2]), mp, "float", fault, chips=4)
print("RESULT", ok, out.checks["logit_err"]["value"], out.device["count"])
"""


@pytest.mark.parametrize("fault", ["sound", "fault"])
def test_exchange_between_devices(tmp_path, fault):
    """On a four-device data mesh (four CPU devices in a child process):
    the sound run is correct, and leaving out the exchange is caught."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO / "src"), str(REPO)]))
    code = _MESH_RUN.format(tests=str(REPO / "tests" / "vbench"))
    proc = subprocess.run([sys.executable, "-c", code, fault,
                           str(tmp_path)], env=env, capture_output=True,
                          text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESULT")]
    assert proc.returncode == 0 and lines, proc.stderr[-3000:]
    _, ok, err, count = lines[-1].split()
    assert count == "4"
    assert ok == ("True" if fault == "sound" else "False"), err
