"""The control of each committed configuration: the plain reference put
in the program's place, in the next precision below the one the
configuration states, must read above one of the configuration's
limits.  Run here at the full widths and depth, on a few images."""

from __future__ import annotations

import json

import numpy as np
import pytest

from _vbench_tiny import REPO
from vbench import harness, spec

IMAGES = 3
CONFIGS = json.loads((REPO / "BENCHMARK.json").read_text())["configs"]


@pytest.mark.parametrize("entry", CONFIGS, ids=[c["name"] for c in CONFIGS])
def test_control_fails_the_limit(entry):
    name = entry["name"]
    cfg = json.loads((REPO / entry["file"]).read_text())
    cell = spec.Cell(name=name, chips=1, config_name=name, config=cfg,
                     traffic_name="", traffic={}, end_to_end=[],
                     per_layer=[], root=REPO)
    g = cfg["geometry"]
    ref = spec.family_module("reference", cfg)
    params = ref.init_params(harness.seed_key(2 ** 31 + 11, 0), g)
    bank = harness.make_bank(2 ** 31 + 11, IMAGES, int(g["image"]))
    want = harness.reference_logits(cell, params, bank)
    control = harness.reference_logits(cell, params, bank,
                                       precision=cfg["correct"]["control"],
                                       calib=bank)
    got = harness.gaps(cell, control, np.arange(IMAGES), want)
    limits = cfg["correct"]["limits"]
    # the control fails at least one of the configuration's numbers
    assert any(got[k] > limits[k] for k in limits), (got, limits)
    # the reference agrees with itself exactly
    assert set(harness.gaps(cell, want, np.arange(IMAGES),
                            want).values()) == {0.0}
