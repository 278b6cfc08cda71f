"""The operation and byte counts of ``vbench/work`` against the
program's own MAC count, at full geometry: DeiT-Ti/16 and ViT-B/16 (the
program's full ``vit_edge``), the two committed configurations; the ViT
family's geometry as the harness reads it from the program; and the
kernel roofline's sum over a forward's layer calls."""

from __future__ import annotations

import json
import types

import pytest

from _vbench_tiny import REPO
from vbench import harness, peaks, readers, spec
from vbench.reference import vit as ref
from vbench.work import vit as work

# operations per image apart from the classifier head, at full geometry
OPS = {"deit_t": 2.49e9, "vit_edge": 46.2e9}

# the program's full models, in the keys of a configuration's geometry
PROGRAM_GEOMETRY = {
    "deit_t": {"image": 224, "patch": 16, "dim": 192, "heads": 3,
               "layers": 12, "mlp_hidden": 768, "n_classes": 1000},
    "vit_edge": {"image": 256, "patch": 16, "dim": 768, "heads": 12,
                 "layers": 12, "mlp_hidden": 3072, "n_classes": 1000},
}


def _geometry(registry):
    from repro.models import vision_registry
    cfg = vision_registry.build_cfg(registry, full=True)
    return cfg, ref.program_geometry(cfg)


@pytest.mark.parametrize("registry", sorted(PROGRAM_GEOMETRY))
def test_program_geometry_reads_the_programs_sizes(registry):
    _, g = _geometry(registry)
    assert g == PROGRAM_GEOMETRY[registry]


@pytest.mark.parametrize("registry", sorted(OPS))
def test_ops_match_twice_the_program_macs(registry):
    from repro.core.perfmodel import count_macs
    from repro.models import vision_registry
    cfg, g = _geometry(registry)
    macs = count_macs(vision_registry.make_spec(cfg))
    want = 2 * (macs.patch_embed + macs.msa + macs.mlp)
    body = work.model_ops_per_image(g) - work.head_ops_per_image(g)
    assert body == pytest.approx(want, rel=1e-12)
    assert body == pytest.approx(OPS[registry], rel=5e-3)


CONFIGS = sorted((REPO / "vbench" / "configs").glob("*.json"))


@pytest.mark.parametrize("path", CONFIGS, ids=[p.stem for p in CONFIGS])
def test_committed_geometry_is_the_programs(path):
    """Each committed configuration against the program's model, by the
    family's ``program_geometry`` and the harness's own comparison."""
    from repro.models import vision_registry
    cfg = json.loads(path.read_text())
    program = vision_registry.build_cfg(cfg["registry"], full=cfg["full"])
    family = spec.family_module("reference", cfg)
    g = family.program_geometry(program)
    assert g and {k: cfg["geometry"][k] for k in g} == g
    harness.check_geometry(program, cfg["geometry"], family)


def test_check_geometry_refuses_a_size_the_program_lacks():
    cfg, g = _geometry("deit_t")
    with pytest.raises(spec.SpecError, match="heads"):
        harness.check_geometry(cfg, dict(g, heads=4), ref)
    with pytest.raises(spec.SpecError, match="mlp_hidden"):
        harness.check_geometry(
            cfg, {k: v for k, v in g.items() if k != "mlp_hidden"}, ref)


def test_layer_call_scales_with_batch_and_keeps_weights_once():
    _, g = _geometry("deit_t")
    ops1, bytes1 = work.layer_call(g, 1, 1)
    ops8, bytes8 = work.layer_call(g, 8, 1)
    assert ops8 == pytest.approx(8 * ops1)
    act = 2 * 196 * 192 * 4
    assert bytes8 - bytes1 == pytest.approx(7 * act)
    _, f32 = work.layer_call(g, 1, 4)
    assert f32 > bytes1


def test_layer_calls_are_the_layers_of_one_forward():
    _, g = _geometry("vit_edge")
    calls = work.layer_calls(g, 8, 4)
    assert calls == [work.layer_call(g, 8, 4)] * 12


def _product_roofline(run):
    """The roofline as one layer call times the layers, the form the
    reader had before it summed the family's layer calls."""
    weight_bytes = 1 if run.arithmetic == "int8" else 4
    peak = run.peaks[run.arithmetic]
    least = 0.0
    for _, _, bucket, _ in run.traced_dispatches:
        ops, nbytes = work.layer_call(run.geometry, bucket // run.chips,
                                      weight_bytes)
        least += max(ops / peak, nbytes / run.peaks["hbm_bytes_s"]) \
            * int(run.geometry["layers"]) * run.chips
    return 100.0 * least / run.trace.kernel_s[readers.KERNEL]


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("registry,arithmetic",
                         [("deit_t", "int8"), ("vit_edge", "bf16")])
def test_roofline_sum_is_the_product(registry, arithmetic, chips):
    """On a synthetic traced run (memory-bound small buckets and
    compute-bound large ones) the sum over the layer calls reads what
    the product did, to the last bits."""
    _, g = _geometry(registry)
    run = types.SimpleNamespace(
        geometry=g, arithmetic=arithmetic, chips=chips, work=work,
        peaks=peaks.peaks("TPU v5 lite"),
        trace=types.SimpleNamespace(kernel_s={readers.KERNEL: 0.4173}),
        traced_dispatches=[(0.0, 1e-3, b, b) for b in (1, 8, 32, 128, 8)])
    got = readers.vita_layer_roofline(run)
    assert got == pytest.approx(_product_roofline(run), rel=1e-12)
    assert got > 0
