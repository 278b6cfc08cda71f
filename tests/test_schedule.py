"""Control-program layer: schedule compilation, the shared executor, Swin
through the batched pipeline (windowed kernels, shifted masks, int8), and
TNT through the same pipeline (inner/outer phases, the pixel batch-fold).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import schedule as sched_lib
from repro.core.quant import (Calibrator, QTensor, ptq_tolerance,
                              quantize_vision_params)
from repro.models import swin, tnt, vision_registry, vit


@pytest.fixture(scope="module")
def swin_setup():
    cfg = swin.swin_edge()
    params = swin.init_params(jax.random.PRNGKey(0), cfg)
    imgs = np.random.default_rng(0).standard_normal(
        (2, cfg.image, cfg.image, 3)).astype(np.float32)
    patches = vit.extract_patches(jnp.asarray(imgs), cfg.patch)
    return cfg, params, patches


@pytest.fixture(scope="module")
def tnt_setup():
    cfg = tnt.tnt_edge()
    params = tnt.init_params(jax.random.PRNGKey(0), cfg)
    imgs = np.random.default_rng(3).standard_normal(
        (2, cfg.image, cfg.image, 3)).astype(np.float32)
    patches = vit.extract_patches(jnp.asarray(imgs), cfg.patch)
    return cfg, params, patches


# ---------------------------------------------------------------------------
# Schedule compilation
# ---------------------------------------------------------------------------


def test_vit_schedule_structure():
    cfg = vit.ViTConfig(name="t", image=32, patch=8, dim=64, heads=4,
                        layers=3, n_classes=10, fused=False)
    s = vit.schedule(cfg)
    assert s.counts() == {"embed": 1, "msa": 3, "mlp": 3, "head": 1}
    embed = s.phases[0]
    assert embed.pos_embed and not embed.norm         # columnar frontend
    for ph in s.phases:
        assert ph.window == 0 and ph.shift == 0       # global MSA only
    msa = [p for p in s.phases if p.kind == "msa"]
    assert [p.path for p in msa] == [("layers", i) for i in range(3)]
    assert all(p.grid == (4, 4) and p.heads == cfg.heads for p in msa)
    # fused (the default): each msa+mlp pair collapses into one layer phase
    fs = vit.schedule(dataclasses.replace(cfg, fused=True))
    assert fs.counts() == {"embed": 1, "layer": 3, "head": 1}
    layers = [p for p in fs.phases if p.kind == "layer"]
    assert [p.path for p in layers] == [p.path for p in msa]
    assert all(p.grid == (4, 4) and p.heads == cfg.heads for p in layers)


def test_swin_schedule_structure():
    cfg = swin.swin_edge(fused=False)                 # 14x14 -> merge -> 7x7
    s = swin.schedule(cfg)
    assert s.counts() == {"embed": 1, "msa": 4, "mlp": 4, "merge": 1,
                          "head": 1}
    embed = s.phases[0]
    assert embed.norm and not embed.pos_embed         # hierarchical frontend
    msa = [p for p in s.phases if p.kind == "msa"]
    assert all(p.window == 7 for p in msa)
    # stage 0 (4 windows): block 1 shifted; stage 1 (1 window): never
    assert [p.shift for p in msa] == [0, 3, 0, 0]
    assert [p.grid for p in msa] == [(14, 14), (14, 14), (7, 7), (7, 7)]
    assert [p.heads for p in msa] == [3, 3, 6, 6]
    assert msa[0].path == ("stages", 0, "blocks", 0)
    merge = next(p for p in s.phases if p.kind == "merge")
    assert merge.path == ("stages", 0) and merge.grid == (14, 14)
    # fused: windowed blocks fuse too, inheriting the msa half's geometry
    fs = swin.schedule(swin.swin_edge())
    assert fs.counts() == {"embed": 1, "layer": 4, "merge": 1, "head": 1}
    layers = [p for p in fs.phases if p.kind == "layer"]
    assert [p.shift for p in layers] == [0, 3, 0, 0]
    assert all(p.window == 7 for p in layers)


def test_full_swin_t_schedule_compiles():
    s = swin.schedule(swin.swin_t(fused=False))
    assert s.counts() == {"embed": 1, "msa": 12, "mlp": 12, "merge": 3,
                          "head": 1}
    shifts = [p.shift for p in s.phases if p.kind == "msa"]
    # last stage is 7x7 = one window -> shift elided there only
    assert shifts == [0, 3] * 5 + [0, 0]
    fs = swin.schedule(swin.swin_t())
    assert fs.counts() == {"embed": 1, "layer": 12, "merge": 3, "head": 1}
    assert [p.shift for p in fs.phases
            if p.kind == "layer"] == shifts


# ---------------------------------------------------------------------------
# Shifted-window mask semantics
# ---------------------------------------------------------------------------


def test_shifted_window_mask_against_coordinate_oracle():
    """mask[w, i, j] == 0 iff both tokens' ORIGINAL (pre-roll) coordinates
    fall in the same contiguous region along both axes — computed here
    independently from source coordinates rather than slice labelling."""
    gh = gw = 14
    win, shift = 7, 3
    mask = sched_lib.shifted_window_mask(gh, gw, win, shift)

    def region(c, size):
        """Contiguity class of an ORIGINAL coordinate: the roll stitches
        [0, shift) (wrapped) after [size-win+shift, size); tokens may only
        attend within their own class."""
        if c < shift:
            return 2
        return 0 if c < size - win + shift else 1

    n_side = gh // win
    for w_id in range(n_side * n_side):
        wr, wc = divmod(w_id, n_side)
        for i in range(win * win):
            for j in range(win * win):
                def orig(t):
                    r, c = divmod(t, win)
                    return ((wr * win + r + shift) % gh,
                            (wc * win + c + shift) % gw)
                (ri, ci), (rj, cj) = orig(i), orig(j)
                same = (region(ri, gh) == region(rj, gh)
                        and region(ci, gw) == region(cj, gw))
                assert (mask[w_id, i, j] == 0.0) == same, (w_id, i, j)


def test_shifted_window_mask_basic_properties():
    m = np.asarray(sched_lib.shifted_window_mask(14, 14, 7, 3))
    assert m.shape == (4, 49, 49)
    np.testing.assert_array_equal(m, m.transpose(0, 2, 1))   # symmetric
    assert (np.diagonal(m, axis1=1, axis2=2) == 0.0).all()   # self-attention
    assert (m < 0).any()                                     # something cut
    z = sched_lib.shifted_window_mask(14, 14, 7, 0)
    assert (np.asarray(z) == 0.0).all()                      # no-shift: open


def test_window_partition_roundtrip_and_order():
    """Partition order must satisfy the kernel's window-id = index % nW
    contract and invert exactly."""
    b, gh, gw, c, win = 2, 4, 4, 3, 2
    x = jnp.arange(b * gh * gw * c, dtype=jnp.float32
                   ).reshape(b, gh, gw, c)
    xw = sched_lib.window_partition(x, win)
    n_w = (gh // win) * (gw // win)
    assert xw.shape == (b * n_w, win * win, c)
    back = sched_lib.window_reverse(xw, win, gh, gw)
    np.testing.assert_array_equal(back, x)
    # row i of the flat axis is window (i % nW) of image (i // nW)
    np.testing.assert_array_equal(xw[n_w], xw.reshape(
        b, n_w, win * win, c)[1, 0])


# ---------------------------------------------------------------------------
# Swin through the batched control program
# ---------------------------------------------------------------------------


def test_swin_schedule_matches_dense_reference(swin_setup):
    cfg, params, patches = swin_setup
    got = swin.forward(params, patches, cfg)
    want = swin.reference_forward(params, patches, cfg)
    assert got.shape == (patches.shape[0], cfg.n_classes)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_swin_pallas_and_xla_backends_agree(swin_setup):
    cfg, params, patches = swin_setup
    a = swin.forward(params, patches, cfg)
    b = swin.forward(params, patches,
                     dataclasses.replace(cfg, backend="pallas"))
    np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4)


def test_swin_shift_changes_result(swin_setup):
    """The shifted block must actually see cross-window context: zeroing
    the shift in the schedule changes the logits."""
    cfg, params, patches = swin_setup
    base = swin.forward(params, patches, cfg)
    s = swin.schedule(cfg)
    phases = tuple(dataclasses.replace(p, shift=0)
                   if p.kind in ("msa", "layer")
                   else p for p in s.phases)
    noshift = sched_lib.run_schedule(
        dataclasses.replace(s, phases=phases), params, patches)
    assert not np.allclose(base, noshift, rtol=1e-3, atol=1e-3)


def test_swin_int8_within_calibration_tolerance(swin_setup):
    cfg, params, patches = swin_setup
    qparams = quantize_vision_params(params)
    cal = Calibrator()
    swin.forward(qparams, patches, cfg, observer=cal)
    cal.freeze()
    qlogits = swin.forward(qparams, patches, cfg, observer=cal)
    logits = swin.forward(params, patches, cfg)
    scale = float(jnp.abs(logits).max())
    err = float(jnp.abs(qlogits - logits).max())
    assert err <= ptq_tolerance(scale), (err, scale)


def test_quantize_vision_params_swin_layout(swin_setup):
    cfg, params, _ = swin_setup
    qp = quantize_vision_params(params)
    b0 = qp["stages"][0]["blocks"][0]
    h = cfg.heads[0]
    dh = cfg.embed_dim // h
    for k in ("wq", "wk", "wv"):
        assert isinstance(b0[k], QTensor)
        assert b0[k].scale.shape == (h, 1, dh)     # per-(head, out-channel)
    assert isinstance(qp["stages"][0]["merge_w"], QTensor)
    assert isinstance(qp["patch_embed"], QTensor)
    # norms, biases and the rel-pos table stay float
    assert not isinstance(b0["rel_bias"], QTensor)
    assert not isinstance(b0["ln1_w"], QTensor)
    assert not isinstance(b0["b_up"], QTensor)


def test_vit_calibration_sites_cover_every_phase():
    """Calibration-site names are schedule-derived and must line up between
    the calibration pass and frozen-scale inference."""
    cfg = vit.ViTConfig(name="t", image=16, patch=8, dim=32, heads=2,
                        layers=2, n_classes=4)
    params = vit.init_params(jax.random.PRNGKey(0), cfg)
    qp = quantize_vision_params(params)
    patches = vit.extract_patches(
        jnp.zeros((1, cfg.image, cfg.image, 3)), cfg.patch)
    cal = Calibrator()
    vit.forward(qp, patches, cfg, observer=cal)
    want = {"patch_embed", "head"}
    for i in range(cfg.layers):
        want |= {f"l{i}.qkv_in", f"l{i}.w_msa", f"l{i}.w_up", f"l{i}.w_down"}
    assert set(cal.amax) == want


# ---------------------------------------------------------------------------
# TNT through the batched control program (inner/outer dual stream)
# ---------------------------------------------------------------------------


def test_tnt_schedule_structure():
    # 4x4 patch grid, 4 pixels/patch
    cfg = tnt.tnt_edge(fused=False)
    s = tnt.schedule(cfg)
    assert s.counts() == {"embed": 1, "inner_msa": 2, "inner_mlp": 2,
                          "fold": 2, "msa": 2, "mlp": 2, "head": 1}
    embed = s.phases[0]
    assert embed.pos_embed and embed.norm             # dual-stream frontend
    assert embed.inner_tokens == cfg.inner_tokens == 4
    # per layer: inner_msa -> inner_mlp -> fold -> msa -> mlp, in order
    kinds = [p.kind for p in s.phases[1:-1]]
    assert kinds == ["inner_msa", "inner_mlp", "fold", "msa", "mlp"] * 2
    inner = [p for p in s.phases if p.kind == "inner_msa"]
    assert [p.path for p in inner] == [("layers", 0, "inner"),
                                       ("layers", 1, "inner")]
    assert all(p.grid == (2, 2) and p.heads == cfg.inner_heads
               and p.window == 0 for p in inner)      # global MSA, pixel grid
    outer = [p for p in s.phases if p.kind == "msa"]
    assert [p.path for p in outer] == [("layers", 0, "outer"),
                                       ("layers", 1, "outer")]
    assert all(p.grid == (4, 4) and p.heads == cfg.heads for p in outer)
    folds = [p for p in s.phases if p.kind == "fold"]
    assert [p.path for p in folds] == [("layers", 0), ("layers", 1)]
    assert [p.site for p in folds] == ["l0.fold", "l1.fold"]
    # fused: BOTH streams' pairs collapse; fold stays its own phase
    fs = tnt.schedule(tnt.tnt_edge())
    assert fs.counts() == {"embed": 1, "inner_layer": 2, "fold": 2,
                           "layer": 2, "head": 1}
    kinds = [p.kind for p in fs.phases[1:-1]]
    assert kinds == ["inner_layer", "fold", "layer"] * 2


def test_full_tnt_s_schedule_compiles():
    s = tnt.schedule(tnt.tnt_s(fused=False))
    assert s.counts() == {"embed": 1, "inner_msa": 12, "inner_mlp": 12,
                          "fold": 12, "msa": 12, "mlp": 12, "head": 1}
    inner = [p for p in s.phases if p.kind == "inner_msa"]
    assert all(p.grid == (4, 4) and p.heads == 4 for p in inner)  # 16 pixels
    assert all(p.grid == (14, 14) for p in s.phases if p.kind == "msa")
    fs = tnt.schedule(tnt.tnt_s())
    assert fs.counts() == {"embed": 1, "inner_layer": 12, "fold": 12,
                           "layer": 12, "head": 1}


def test_pixel_partition_against_coordinate_oracle():
    """pixel_partition row r, token t, element k must address the image
    pixel the docstring promises — computed here independently from source
    coordinates (the analogue of the shifted-window mask oracle)."""
    b, image, patch, m = 2, 16, 8, 4
    side, ms = image // patch, int(np.sqrt(m))
    ip = patch // ms
    n = side * side
    # encode every pixel's identity: value = ((b * R + r) * C + c) * 3 + ch
    img = np.arange(b * image * image * 3, dtype=np.float32
                    ).reshape(b, image, image, 3)
    patches = vit.extract_patches(jnp.asarray(img), patch)
    sub = np.asarray(sched_lib.pixel_partition(patches, m))
    assert sub.shape == (b * n, m, ip * ip * 3)
    for r in range(b * n):
        b_i, p_i = divmod(r, n)
        pr, pc = divmod(p_i, side)
        for t in range(m):
            sr, sc = divmod(t, ms)
            for k in range(ip * ip * 3):
                q, ch = divmod(k, 3)
                qr, qc = divmod(q, ip)
                row = pr * patch + sr * ip + qr
                col = pc * patch + sc * ip + qc
                want = ((b_i * image + row) * image + col) * 3 + ch
                assert sub[r, t, k] == want, (r, t, k)


def test_pixel_partition_rejects_bad_geometry():
    patches = jnp.zeros((1, 4, 8 * 8 * 3))
    with pytest.raises(AssertionError):
        sched_lib.pixel_partition(patches, 3)          # not a square
    with pytest.raises(AssertionError):
        sched_lib.pixel_partition(patches, 9)          # 8 % 3 != 0


def test_tnt_schedule_matches_dense_reference(tnt_setup):
    cfg, params, patches = tnt_setup
    got = tnt.forward(params, patches, cfg)
    want = tnt.reference_forward(params, patches, cfg)
    assert got.shape == (patches.shape[0], cfg.n_classes)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_tnt_pallas_and_xla_backends_agree(tnt_setup):
    cfg, params, patches = tnt_setup
    a = tnt.forward(params, patches, cfg)
    b = tnt.forward(params, patches,
                    dataclasses.replace(cfg, backend="pallas"))
    np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4)


def test_tnt_inner_blocks_change_result(tnt_setup):
    """The inner stream must actually feed the outer one: skipping the
    inner/fold phases changes the logits."""
    cfg, params, patches = tnt_setup
    base = tnt.forward(params, patches, cfg)
    s = tnt.schedule(cfg)
    pruned = tuple(p for p in s.phases
                   if p.kind not in ("inner_msa", "inner_mlp",
                                     "inner_layer", "fold"))
    no_inner = sched_lib.run_schedule(
        dataclasses.replace(s, phases=pruned), params, patches)
    assert not np.allclose(base, no_inner, rtol=1e-3, atol=1e-3)


def test_tnt_int8_within_calibration_tolerance(tnt_setup):
    cfg, params, patches = tnt_setup
    qparams = quantize_vision_params(params)
    cal = Calibrator()
    tnt.forward(qparams, patches, cfg, observer=cal)
    cal.freeze()
    qlogits = tnt.forward(qparams, patches, cfg, observer=cal)
    logits = tnt.forward(params, patches, cfg)
    scale = float(jnp.abs(logits).max())
    err = float(jnp.abs(qlogits - logits).max())
    assert err <= ptq_tolerance(scale), (err, scale)


def test_quantize_vision_params_tnt_layout(tnt_setup):
    cfg, params, _ = tnt_setup
    qp = quantize_vision_params(params)
    l0 = qp["layers"][0]
    # inner and outer QKV both per-(head, out-channel), via the same keys
    for blk, h, dh in ((l0["inner"], cfg.inner_heads, cfg.inner_head_dim),
                       (l0["outer"], cfg.heads, cfg.head_dim)):
        for k in ("wq", "wk", "wv"):
            assert isinstance(blk[k], QTensor)
            assert blk[k].scale.shape == (h, 1, dh)
        assert isinstance(blk["w_msa"], QTensor)
    # TNT-specific projections are per-channel; positions/norms stay float
    assert isinstance(qp["pixel_embed"], QTensor)
    assert isinstance(l0["fold_w"], QTensor)
    assert not isinstance(qp["inner_pos_embed"], QTensor)
    assert not isinstance(qp["pos_embed"], QTensor)
    assert not isinstance(l0["fold_ln_w"], QTensor)
    assert not isinstance(l0["fold_b"], QTensor)


def test_tnt_calibration_sites_cover_every_phase(tnt_setup):
    """Both streams' matmuls must calibrate: inner sites are prefixed
    l{i}.inner, the fold l{i}.fold, the frontend pixel_embed."""
    cfg, params, patches = tnt_setup
    qp = quantize_vision_params(params)
    cal = Calibrator()
    tnt.forward(qp, patches[:1], cfg, observer=cal)
    want = {"pixel_embed", "patch_embed", "head"}
    for i in range(cfg.layers):
        for pre in (f"l{i}", f"l{i}.inner"):
            want |= {f"{pre}.qkv_in", f"{pre}.w_msa",
                     f"{pre}.w_up", f"{pre}.w_down"}
        want.add(f"l{i}.fold")
    assert set(cal.amax) == want


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_registry_lists_the_paper_families():
    # the four paper families plus their head-pruned serving variants
    assert set(vision_registry.list_models()) == {
        "vit_edge", "deit_t", "swin_t", "tnt_s",
        "vit_edge_p", "deit_t_p", "swin_t_p", "tnt_s_p"}
    # sorted -> deterministic CLI/bench ordering across runs
    assert list(vision_registry.list_models()) == \
        sorted(vision_registry.list_models())
    with pytest.raises(KeyError):
        vision_registry.get("resnet50")


@pytest.mark.parametrize("name", ["vit_edge", "deit_t", "swin_t", "tnt_s"])
def test_registry_builds_and_schedules(name):
    cfg = vision_registry.build_cfg(name)
    s = vision_registry.make_schedule(cfg)
    assert s.phases[0].kind == "embed" and s.phases[-1].kind == "head"
    full = vision_registry.build_cfg(name, full=True)
    fs = vision_registry.make_schedule(full)
    assert len(fs.phases) >= len(s.phases)
    # backend override lands in both the config and the compiled schedule
    bcfg = vision_registry.build_cfg(name, backend="pallas")
    assert vision_registry.make_schedule(bcfg).backend == "pallas"


# ---------------------------------------------------------------------------
# Named scopes per phase
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fused", [True, False])
def test_phases_run_under_named_scopes(monkeypatch, fused):
    """The lowered program names every phase's scope in its op metadata
    (``embed``, ``layer<i>`` or ``msa<i>`` / ``mlp<i>``, ``head``), and
    the scopes change no operation: without them the program is the
    same."""
    import contextlib

    cfg = dataclasses.replace(vision_registry.build_cfg(
        "vit_edge", backend="xla"), fused=fused)
    sched = vision_registry.make_schedule(cfg)
    scopes = sched_lib.phase_scopes(sched)
    layers = [f"layer{i}" for i in range(cfg.layers)] if fused else \
        [f"{k}{i}" for i in range(cfg.layers) for k in ("msa", "mlp")]
    assert sorted(scopes) == sorted(["embed", "head"] + layers)
    assert scopes[0] == "embed" and scopes[-1] == "head"
    params = vit.init_params(jax.random.PRNGKey(0), cfg)
    patches = jax.ShapeDtypeStruct(
        (2, (cfg.image // cfg.patch) ** 2, cfg.patch * cfg.patch * 3),
        jnp.float32)

    def lower():
        return jax.jit(lambda p, x: vit.forward(p, x, cfg)).lower(
            params, patches)

    scoped = lower()
    text = scoped.as_text(debug_info=True)
    for scope in scopes:
        assert f"/{scope}/" in text, scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = lower()
    assert "/layer0/" not in plain.as_text(debug_info=True)
    assert scoped.as_text() == plain.as_text()


@pytest.mark.parametrize("fused", [True, False])
def test_window_fold_and_unfold_run_under_their_scopes(monkeypatch, fused):
    """Each windowed phase's shift, window fold and relative-bias gather
    carry ``<phase>/fold`` in their op metadata, and the unfold and
    unshift ``<phase>/unfold``; the kernel's own operations carry
    neither, and the scopes change no operation."""
    import contextlib

    cfg = dataclasses.replace(vision_registry.build_cfg(
        "swin_t", backend="xla"), fused=fused)
    sched = vision_registry.make_schedule(cfg)
    windowed = [scope for ph, scope in zip(sched.phases,
                                           sched_lib.phase_scopes(sched))
                if ph.window]
    assert windowed == ([f"layer{i}" for i in range(4)] if fused else
                        [f"msa{i}" for i in range(4)])
    assert any(ph.shift for ph in sched.phases)
    params = swin.init_params(jax.random.PRNGKey(0), cfg)
    patches = jax.ShapeDtypeStruct(
        (2, (cfg.image // cfg.patch) ** 2, cfg.patch * cfg.patch * 3),
        jnp.float32)

    def lower():
        return jax.jit(lambda p, x: swin.forward(p, x, cfg)).lower(
            params, patches)

    scoped = lower()
    text = scoped.as_text(debug_info=True)
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))

    def where(op):
        """The location of each operation ``op`` in the lowered text."""
        return [locs[m] for m in re.findall(
            rf"{op}\b.*loc\((#loc\d+)\)", text)]

    for scope in windowed:
        assert f"/{scope}/fold/gather" in text, scope
        assert f"/{scope}/unfold/" in text, scope
    # the softmax of the windowed attention is no glue
    exps = where("stablehlo.exponential")
    assert len(exps) == 4
    assert not any("/fold/" in e or "/unfold/" in e for e in exps)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = lower()
    assert "/fold/" not in plain.as_text(debug_info=True)
    assert scoped.as_text() == plain.as_text()
