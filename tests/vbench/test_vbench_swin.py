"""The Swin family of the benchmark on the CPU: the program served
through the harness against the plain reference
(``vbench/reference/swin.py``) on the reference's seeded weights, three
faults of the windowed path that must turn ``correct`` false, the
geometry check, and the operations and bytes of ``vbench/work/swin.py``.

The runs go through ``harness.run_cell`` on the ``xla`` backend, whose
float32 matrix products the CPU computes in full float32; so the
reference here is ``f32``, and the limit is the committed
``swin_t_float`` configuration's, the one the chip cell holds the
program to.
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from _vbench_tiny import REPO, closed, config, make_root, on_cpu

COMMITTED = json.loads((REPO / "vbench" / "configs" / "swin_t_float.json")
                       .read_text())
LIMIT = COMMITTED["correct"]["limits"]["logit_err"]
# the program's reduced swin_t: 56 px, a shifted stage of four 7x7
# windows, one merge, a stage of one window
REDUCED = {"image": 56, "patch": 4, "embed_dim": 48, "depths": [2, 2],
           "heads": [3, 6], "window": 7, "mlp_ratio": 4.0, "n_classes": 10,
           "ln_eps": 1e-05}
# three stages of 4x4 windows (64, 16 and 4 of them), every stage shifted
THREE_STAGES = {"image": 64, "patch": 2, "embed_dim": 32, "depths": [2, 2, 2],
                "heads": [1, 2, 4], "window": 4, "mlp_ratio": 4.0,
                "n_classes": 10, "ln_eps": 1e-05}
# the program's float32 on the CPU against the reference's: the same
# arithmetic in another order of sums, far below any limit
AGREE = 1e-5


@pytest.fixture
def three_stages(monkeypatch):
    """A registry entry whose reduced model has ``THREE_STAGES``."""
    from repro.models import swin, vision_registry

    def reduced():
        g = THREE_STAGES
        return swin.SwinConfig(
            name="swin_3s", image=g["image"], patch=g["patch"],
            embed_dim=g["embed_dim"], depths=tuple(g["depths"]),
            heads=tuple(g["heads"]), window=g["window"],
            n_classes=g["n_classes"])

    monkeypatch.setitem(vision_registry._REGISTRY, "swin_3s",
                        vision_registry.VisionModel(
                            name="swin_3s", family="swin", description="",
                            reduced=reduced, full=reduced))
    return "swin_3s"


def _run(tmp_path, monkeypatch, registry="swin_t", geometry=REDUCED):
    from vbench import harness
    on_cpu(monkeypatch)
    cfg = config("float", LIMIT, registry=registry, family="swin",
                 geometry=geometry)
    cfg["correct"]["reference"] = "f32"
    cfg["buckets"] = [4]
    root = make_root(tmp_path, {"swin.closed": (cfg, closed(), 1)},
                     metrics=("images_per_s", "setup_s"))
    return harness.run_cell(root, "swin.closed", 2 ** 31 + 16, 0.5, False)


@pytest.mark.parametrize("geometry", ["reduced", "three_stages"])
def test_program_agrees_with_the_reference(tmp_path, monkeypatch, request,
                                           geometry):
    from vbench import harness
    registry = "swin_t" if geometry == "reduced" else \
        request.getfixturevalue("three_stages")
    out = _run(tmp_path, monkeypatch, registry,
               REDUCED if geometry == "reduced" else THREE_STAGES)
    assert harness.passed(out.checks), out.checks
    assert out.failed == 0 and out.attempted > 0
    assert out.checks["logit_err"]["value"] < AGREE
    assert out.metrics["images_per_s"]["value"] > 0


def _shift_dropped(monkeypatch):
    """The control program forgets the shifted windows: every block runs
    on the plain windows, with no mask."""
    from repro.models import swin
    schedule = swin.schedule

    def unshifted(cfg):
        s = schedule(cfg)
        return dataclasses.replace(s, phases=tuple(
            dataclasses.replace(p, shift=0) for p in s.phases))

    monkeypatch.setattr(swin, "schedule", unshifted)


def _layer_operand_zeroed(monkeypatch, position):
    """The fused windowed layer gets zeros for one of its operands."""
    import jax.numpy as jnp
    from repro.kernels import ops
    layer = ops.vita_layer_fused

    def broken(*args, **kw):
        args = list(args)
        args[position] = jnp.zeros_like(args[position])
        return layer(*args, **kw)

    monkeypatch.setattr(ops, "vita_layer_fused", broken)


FAULTS = {
    "shift_dropped": _shift_dropped,
    # vita_layer_fused(x, wq, wk, wv, w_msa, ln1_w, ln1_b, ln2_w, ln2_b,
    #                  w_up, b_up, w_down, b_down, bias, mask)
    "bias_zeroed": lambda mp: _layer_operand_zeroed(mp, 13),
    "mask_zeroed": lambda mp: _layer_operand_zeroed(mp, 14),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_windowed_path_is_caught(tmp_path, monkeypatch, fault):
    from vbench import harness
    FAULTS[fault](monkeypatch)
    out = _run(tmp_path, monkeypatch)
    assert not harness.passed(out.checks), out.checks
    assert out.checks["logit_err"]["value"] > LIMIT


def test_limit_lies_between_agreement_and_the_control():
    """At the reduced size on the CPU: bfloat16 operands (the stated
    precision) stay under the committed limit, and the int8 control
    reads above it."""
    from vbench import harness
    from vbench.reference import swin as ref
    params = ref.init_params(harness.seed_key(2 ** 31 + 16, 0), REDUCED)
    bank = harness.make_bank(2 ** 31 + 16, 8, REDUCED["image"])
    want = ref.forward(params, bank, REDUCED, precision="f32")
    scale = np.abs(want).max()

    def gap(precision, scales=None):
        got = ref.forward(params, bank, REDUCED, precision=precision,
                          act_scales=scales)
        return float(np.abs(got - want).max() / scale)

    assert gap("f32_bf16dot") < LIMIT
    assert gap("int8", ref.calibrate(params, bank, REDUCED, "int8")) > LIMIT


def _program():
    from repro.models import vision_registry
    return vision_registry.build_cfg("swin_t", full=True)


def test_program_geometry_is_the_published_swin_t():
    from vbench.reference import swin as ref
    g = ref.program_geometry(_program())
    assert g == {k: v for k, v in COMMITTED["geometry"].items()
                 if k != "ln_eps"}


@pytest.mark.parametrize("key,wrong", [("depths", [2, 2, 2, 2]),
                                       ("heads", [3, 6, 12, 12])])
def test_geometry_check_refuses_a_wrong_stage(key, wrong):
    from vbench import harness, spec
    from vbench.reference import swin as ref
    g = dict(COMMITTED["geometry"], **{key: wrong})
    with pytest.raises(spec.SpecError, match=key):
        harness.check_geometry(_program(), g, ref)


def test_reference_index_and_mask_follow_the_paper():
    """The relative index of a 2x2 window, written out, and the region
    mask of a 4x4 map shifted by 1 (regions cut at rows and columns 2
    and 3): the window at the bottom right holds four regions."""
    from vbench.reference import swin as ref
    # offsets (dy, dx) -> (dy + 1) * 3 + (dx + 1) for tokens 0..3
    assert ref.relative_index(2).tolist() == [[4, 3, 1, 0], [5, 4, 2, 1],
                                              [7, 6, 4, 3], [8, 7, 5, 4]]
    m = ref.region_mask(4, 2, 1)
    assert m.shape == (4, 4, 4)
    assert not m[0].any()
    blocked = m[3] != 0
    # tokens (2,2), (2,3), (3,2), (3,3): four regions, none shares
    assert blocked.sum() == 12 and not np.diag(blocked).any()


def test_layer_calls_at_the_published_geometry():
    from vbench.work import swin as work
    g = COMMITTED["geometry"]
    calls = work.layer_calls(g, 32, 4)
    assert len(calls) == 12
    stages = work.stages(g)
    assert [s[1] for s in stages] == [96, 192, 384, 768]
    assert [s[3] for s in stages] == [64, 16, 4, 1]
    # per stage: its depth of identical calls, q/k/v, scores, scores x v,
    # output projection and MLP over 32 x windows of 49 tokens
    want = []
    for depth, d, n_w in ((2, 96, 64), (2, 192, 16), (6, 384, 4),
                          (2, 768, 1)):
        macs = 32 * n_w * (4 * 49 * d * d + 2 * 49 * 49 * d
                           + 2 * 49 * d * 4 * d)
        want += [2.0 * macs] * depth
    assert [ops for ops, _ in calls] == pytest.approx(want, rel=1e-12)
    # bytes: weights once, then the activation in and out per image
    (_, b1), (_, b2) = work.layer_calls(g, 1, 4)[0], \
        work.layer_calls(g, 2, 4)[0]
    assert b2 - b1 == pytest.approx(2 * 64 * 49 * 96 * 4)
    assert b1 > 4 * (4 * 96 * 96 + 2 * 96 * 384)


def test_model_ops_against_the_hand_count():
    """Swin-T at 224 px, counted by hand: patch embedding 56^2 tokens x
    48 x 96; per stage depth x windows x (4nd^2 + 2n^2 d + 8nd^2) with
    n 49; merges 28^2 x 384 x 192, 14^2 x 768 x 384, 7^2 x 1536 x 768;
    head 768 x 1000.  About 4.49 G multiply-accumulates (the paper's
    4.5 GFLOPs), 8.98 G operations."""
    from vbench.work import swin as work
    macs = (3136 * 48 * 96
            + 2 * 64 * (4 * 49 * 96 ** 2 + 2 * 49 ** 2 * 96
                        + 8 * 49 * 96 ** 2)
            + 2 * 16 * (4 * 49 * 192 ** 2 + 2 * 49 ** 2 * 192
                        + 8 * 49 * 192 ** 2)
            + 6 * 4 * (4 * 49 * 384 ** 2 + 2 * 49 ** 2 * 384
                       + 8 * 49 * 384 ** 2)
            + 2 * 1 * (4 * 49 * 768 ** 2 + 2 * 49 ** 2 * 768
                       + 8 * 49 * 768 ** 2)
            + 784 * 384 * 192 + 196 * 768 * 384 + 49 * 1536 * 768
            + 768 * 1000)
    got = work.model_ops_per_image(COMMITTED["geometry"])
    assert got == 2.0 * macs
    assert got == pytest.approx(8.98e9, rel=1e-3)
