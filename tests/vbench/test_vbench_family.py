"""A model family other than ViT joins the benchmark by new files alone.

A whole run of the harness on the CPU over the program's reduced Swin
(``swin_t``: 56 px, two stages of shifted 7x7 windows, one patch
merge) through the ``xla`` backend.  The cell's files are new files in a
temporary root; the family's two modules, ``vbench.reference.<family>``
and ``vbench.work.<family>``, are stand-ins put into ``sys.modules``.
The stand-ins lean on the program's own Swin code (its weights and its
dense ``reference_forward``): this checks the harness's plumbing, not
the model, and is no reference for a Swin cell.
"""

from __future__ import annotations

import json
import math
import sys
import types

import pytest

from _vbench_tiny import REPO, closed, config, make_root, on_cpu

FAMILY = "swin_stand_in"
# the program's reduced swin_t, in the keys the stand-in reads back
GEOMETRY = {"image": 56, "patch": 4, "embed_dim": 48, "depths": [2, 2],
            "heads": [3, 6], "window": 7, "mlp_ratio": 4.0,
            "n_classes": 10}
LIMIT = json.loads((REPO / "vbench" / "configs" / "vit_b16_float.json")
                   .read_text())["correct"]["limits"]["logit_err"]


def _program_cfg():
    from repro.models import vision_registry
    return vision_registry.build_cfg("swin_t")


def _reference():
    import jax
    import numpy as np
    from repro.models import swin, vit
    program = _program_cfg()
    dense = jax.jit(lambda p, x: swin.reference_forward(
        p, vit.extract_patches(x, program.patch), program))
    mod = types.ModuleType(f"vbench.reference.{FAMILY}")
    mod.QUANT_MAX = {}
    init = jax.jit(lambda key: swin.init_params(key, program))
    mod.init_params = lambda key, g: init(key)
    mod.forward = lambda params, images, g, *, precision, act_scales: \
        np.asarray(dense(params, np.asarray(images)))
    mod.program_geometry = lambda cfg: {
        "image": cfg.image, "patch": cfg.patch, "embed_dim": cfg.embed_dim,
        "depths": cfg.depths, "heads": cfg.heads, "window": cfg.window,
        "mlp_ratio": cfg.mlp_ratio, "n_classes": cfg.n_classes}
    return mod


def _work():
    """Per stage, ``depth`` windowed layer calls at the stage's width:
    q/k/v, scores, scores x v, the output projection and the MLP over
    every window, weights read once."""
    from repro.models import swin
    stages = swin.to_spec(_program_cfg()).stages

    def layer_calls(g, batch, weight_bytes):
        calls = []
        for st in stages:
            n, d = st.tokens, st.dim
            m = int(d * st.mlp_ratio)
            macs = st.n_windows * (4 * n * d * d + 2 * n * n * d
                                   + 2 * n * d * m)
            act = 2.0 * batch * st.n_windows * n * d * 4
            calls += [(2.0 * batch * macs,
                       (4 * d * d + 2 * d * m) * weight_bytes + act)
                      ] * st.layers
        return calls

    mod = types.ModuleType(f"vbench.work.{FAMILY}")
    mod.layer_calls = layer_calls
    mod.model_ops_per_image = lambda g: sum(
        ops for ops, _ in layer_calls(g, 1, 4))
    return mod


@pytest.fixture
def family(monkeypatch):
    monkeypatch.setitem(sys.modules, f"vbench.reference.{FAMILY}",
                        _reference())
    monkeypatch.setitem(sys.modules, f"vbench.work.{FAMILY}", _work())


def _run(tmp_path, monkeypatch, geometry):
    from vbench import harness
    on_cpu(monkeypatch)
    cfg = config("float", LIMIT, registry="swin_t", family=FAMILY,
                 geometry=geometry)
    cfg["correct"]["reference"] = "f32"
    cfg["buckets"] = [1, 4]
    root = make_root(tmp_path, {"swin.closed": (cfg, closed(), 1)},
                     metrics=("images_per_s", "setup_s"))
    return harness.run_cell(root, "swin.closed", 2 ** 31 + 11, 0.5, False)


def test_swin_runs_through_the_harness(tmp_path, monkeypatch, family):
    from vbench import harness
    out = _run(tmp_path, monkeypatch, GEOMETRY)
    assert harness.passed(out.checks), out.checks
    assert out.failed == 0 and out.attempted > 0
    for name in ("images_per_s", "setup_s"):
        v = out.metrics[name]["value"]
        assert math.isfinite(v) and v > 0, (name, v)


def test_wrong_stage_depth_is_refused_at_set_up(tmp_path, monkeypatch,
                                                 family):
    from vbench import spec
    with pytest.raises(spec.SpecError, match="depths"):
        _run(tmp_path, monkeypatch, dict(GEOMETRY, depths=[2, 3]))


def test_roofline_sums_windowed_calls_of_each_stage(family):
    """The shared kernel roofline over a geometry with no ``layers``:
    each call at its own stage's width and window count."""
    from vbench import peaks, readers, spec
    work = spec.family_module("work", {"family": FAMILY})
    calls = work.layer_calls(GEOMETRY, 4, 4)
    assert len(calls) == 4 and calls[0] != calls[-1]
    pk = peaks.peaks("TPU v5 lite")
    run = types.SimpleNamespace(
        geometry=GEOMETRY, arithmetic="bf16", chips=1, work=work, peaks=pk,
        trace=types.SimpleNamespace(kernel_s={readers.KERNEL: 2e-3}),
        traced_dispatches=[(0.0, 1e-3, 4, 3), (1e-3, 2e-3, 4, 4)])
    least = 2 * sum(max(ops / pk["bf16"], nbytes / pk["hbm_bytes_s"])
                    for ops, nbytes in calls)
    assert readers.vita_layer_roofline(run) == \
        pytest.approx(100.0 * least / 2e-3, rel=1e-12)
