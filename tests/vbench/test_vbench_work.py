"""The operation and byte counts of ``vbench/work`` against the
program's own MAC count, at full geometry: DeiT-Ti/16 and ViT-B/16 (the
program's full ``vit_edge``), the two committed configurations."""

from __future__ import annotations

import json

import pytest

from _vbench_tiny import REPO
from vbench.work import vit as work

# operations per image apart from the classifier head, at full geometry
OPS = {"deit_t": 2.49e9, "vit_edge": 46.2e9}


def _geometry(registry):
    from repro.models import vision_registry
    cfg = vision_registry.build_cfg(registry, full=True)
    return cfg, {"image": cfg.image, "patch": cfg.patch, "dim": cfg.dim,
                 "heads": cfg.heads, "layers": cfg.layers,
                 "mlp_hidden": cfg.mlp_hidden, "n_classes": cfg.n_classes}


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
    cfg = json.loads(path.read_text())
    _, g = _geometry(cfg["registry"])
    assert {k: cfg["geometry"][k] for k in g} == g


def test_layer_call_scales_with_batch_and_keeps_weights_once():
    _, g = _geometry("deit_t")
    ops1, bytes1 = work.layer_call(g, 1, 1)
    ops8, bytes8 = work.layer_call(g, 8, 1)
    assert ops8 == pytest.approx(8 * ops1)
    act = 2 * 196 * 192 * 4
    assert bytes8 - bytes1 == pytest.approx(7 * act)
    _, f32 = work.layer_call(g, 1, 4)
    assert f32 > bytes1
