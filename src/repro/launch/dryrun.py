import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: prove the distribution config is coherent.

For every applicable (arch x shape) cell and both production meshes
(16x16 single pod, 2x16x16 multi-pod), this script:

  1. builds the step function (train / prefill / decode per the cell kind),
  2. jits it with explicit in/out shardings from distributed/sharding.py,
  3. ``.lower()``s against ShapeDtypeStruct stand-ins (zero allocation),
  4. ``.compile()``s — any sharding mismatch / unsupported collective /
     compile-time OOM fails loudly here,
  5. records memory_analysis / cost_analysis / a collective-bytes breakdown
     parsed from the partitioned HLO into results/dryrun/<cell>.json.

The roofline analysis (benchmarks/roofline.py) and EXPERIMENTS.md §Dry-run
read these JSONs.  Variants (--variant remat=1,...) support the §Perf
iteration loop.

NOTE: the XLA_FLAGS line above MUST run before any other jax import — jax
locks the device count at first backend init.  Do not set this flag
globally; tests and benches must see 1 device.
"""

import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.distributed import sharding as shd
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.models import transformer as tr


def _mesh_context(mesh):
    """Ambient-mesh context across jax versions."""
    if hasattr(jax.sharding, "use_mesh"):
        return jax.sharding.use_mesh(mesh)
    if hasattr(jax, "set_mesh"):
        return jax.set_mesh(mesh)
    return mesh   # Mesh is itself a context manager (legacy)

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_DEF_RE = re.compile(r"^\s*(%?[\w.\-]+) = (.+?) (?:(%?[\w.\-]+-start|"
                     r"[\w\-]+)\()")


def _type_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, total_devices: int) -> Optional[int]:
    m = re.search(r"replica_groups=\{\{([0-9, ]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    return None


def parse_collectives(hlo_text: str, total_devices: int) -> Dict[str, Any]:
    """Per-device collective traffic from partitioned HLO text."""
    stats = {"bytes_total": 0, "by_kind": {}, "by_group_size": {},
             "op_count": 0, "top_ops": []}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = re.search(r"=\s*(\(?[a-z0-9\[\],{}\s]+?\)?)\s+"
                      r"((?:%s)(?:-start)?)\(" % "|".join(_COLLECTIVES),
                      line)
        if not m:
            continue
        out_type, kind = m.group(1), m.group(2).replace("-start", "")
        nbytes = _type_bytes(out_type)
        gs = _group_size(line, total_devices) or 1
        # ring-model traffic factors (bytes on the wire per device)
        if kind == "all-reduce":
            wire = 2.0 * (gs - 1) / max(gs, 1) * nbytes
        elif kind == "all-gather":
            wire = (gs - 1) / max(gs, 1) * nbytes        # output = gathered
        elif kind == "reduce-scatter":
            wire = (gs - 1) * nbytes                     # output = shard
        elif kind == "all-to-all":
            wire = (gs - 1) / max(gs, 1) * nbytes
        else:  # collective-permute
            wire = float(nbytes)
        stats["bytes_total"] += int(wire)
        stats["by_kind"][kind] = stats["by_kind"].get(kind, 0) + int(wire)
        key = str(gs)
        stats["by_group_size"][key] = (stats["by_group_size"].get(key, 0)
                                       + int(wire))
        stats["op_count"] += 1
        stats["top_ops"].append((int(wire), kind, gs,
                                 out_type.strip()[:64]))
    stats["top_ops"] = sorted(stats["top_ops"], reverse=True)[:10]
    return stats


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


def _apply_variant(cfg, variant: str):
    """'remat=1,dtype=float32' -> dataclasses.replace on the config."""
    if not variant:
        return cfg
    kw = {}
    for item in variant.split(","):
        if not item:
            continue
        k, v = item.split("=")
        field = {f.name: f for f in dataclasses.fields(cfg)}[k]
        if field.type in ("bool", bool):
            kw[k] = v not in ("0", "false", "False")
        elif field.type in ("int", int) or k in ("window",):
            kw[k] = int(v)
        else:
            kw[k] = v
    return dataclasses.replace(cfg, **kw)


def _tree_bytes_per_device(shape_tree, spec_tree, mesh) -> int:
    axis = dict(zip(mesh.axis_names, mesh.devices.shape))

    def leaf_bytes(leaf, spec):
        denom = 1
        for ax in spec:
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                denom *= axis[a]
        n = 1
        for s in leaf.shape:
            n *= s
        return n * leaf.dtype.itemsize // max(denom, 1)

    flat_l, treedef = jax.tree_util.tree_flatten(shape_tree)
    flat_s = treedef.flatten_up_to(spec_tree)
    return int(sum(leaf_bytes(l, s) for l, s in zip(flat_l, flat_s)))


def analytic_activation_bytes(cfg, cell, mesh) -> int:
    """Per-device activation HBM traffic estimate for ONE forward pass
    (bf16, write+read once), with the Pallas kernel execution model: no
    (S,S) score materialization, ff intermediates sharded over `model`.
    Used by the roofline's adjusted memory term (see benchmarks/roofline.py
    for the fwd/bwd multipliers)."""
    axis = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = axis.get("pod", 1) * axis.get("data", 1)
    tp = axis.get("model", 1)
    if cell.kind == "decode":
        tokens_dev = max(cell.global_batch // dp, 1)
    else:
        tokens_dev = max(cell.global_batch * cell.seq_len // dp, 1)
    d = cfg.d_model
    per_layer = {}
    per_layer["attn"] = 6 * d + (2 * cfg.n_heads * cfg.hd +
                                 2 * cfg.n_kv_heads * cfg.hd) // tp
    per_layer["rec"] = 6 * d + 6 * (cfg.lru_width or d) // tp
    per_layer["mlstm"] = 6 * d + 12 * d // tp
    per_layer["slstm"] = 6 * d + 8 * d
    ff = (cfg.moe.d_ff * cfg.moe.top_k * 3 if cfg.moe
          else cfg.d_ff * (3 if cfg.gated else 2))
    elems = 0
    for kind in cfg.pattern:
        elems += per_layer[kind] + ff // tp + 2 * d
    elems *= cfg.n_superblocks
    # unembed logits (fp32 cast) once
    logits = tokens_dev * cfg.padded_vocab // tp * 4 if cell.kind != \
        "decode" else 0
    return int(2 * tokens_dev * elems * 2 + logits)   # write+read, bf16


def lower_cell(arch: str, shape: str, mesh, *, variant: str = "",
               donate: bool = True) -> Dict[str, Any]:
    cfg = _apply_variant(configs.get(arch), variant)
    cell = configs.SHAPES[shape]
    n_dev = mesh.devices.size
    t0 = time.time()

    params_shape = jax.eval_shape(
        lambda: tr.init_params(jax.random.PRNGKey(0), cfg))
    pspec = shd.param_specs(cfg, params_shape, mesh)
    if cfg.fsdp:
        pspec = shd.fsdp_widen(pspec, params_shape, mesh)
    pshard = shd.named(pspec, mesh)
    repl = NamedSharding(mesh, P())

    if cell.kind == "train":
        batch_shape = configs.train_inputs(cfg, cell)
        bspec = shd.train_batch_specs(cfg, batch_shape, mesh)
        bshard = {k: NamedSharding(mesh, s) for k, s in bspec.items()}
        opt_shape = jax.eval_shape(steps_lib.init_opt_state, params_shape)
        ospec = {"adam": shd.opt_state_specs(pspec, params_shape, mesh)}
        oshard = shd.named(ospec, mesh)
        step_fn = steps_lib.make_train_step(cfg)
        jfn = jax.jit(
            step_fn,
            in_shardings=(pshard, oshard, bshard, repl),
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1) if donate else ())
        args = (params_shape, opt_shape, batch_shape,
                jax.ShapeDtypeStruct((), jnp.int32))
        state_bytes = (_tree_bytes_per_device(params_shape, pspec, mesh) +
                       _tree_bytes_per_device(
                           opt_shape, ospec, mesh))
        tokens = cell.global_batch * cell.seq_len
    elif cell.kind == "prefill":
        batch_shape = {k: v for k, v in
                       configs.prefill_inputs(cfg, cell).items()
                       if k != "labels"}
        bspec = shd.train_batch_specs(cfg, batch_shape, mesh)
        bshard = {k: NamedSharding(mesh, s) for k, s in bspec.items()}
        step_fn = steps_lib.make_prefill_step(cfg, cell.seq_len)
        # Declare the output KV-cache shardings (same specs the decode step
        # consumes).  Leaving them unspecified lets XLA replicate/reshard
        # the multi-hundred-GB cache tree — measured 19x collective blowup
        # on qwen prefill_32k (EXPERIMENTS.md §Perf iteration 1).
        caches_shape = jax.eval_shape(
            lambda: tr.init_caches(cfg, cell.global_batch, cell.seq_len))
        cspec = tuple(
            shd.cache_spec_tree(cfg, cs, mesh, cell.global_batch)
            for cs in caches_shape)
        cshard = shd.named(cspec, mesh)
        tok_spec = NamedSharding(
            mesh, P(shd._batch_axis(cell.global_batch, mesh)))
        jfn = jax.jit(step_fn, in_shardings=(pshard, bshard),
                      out_shardings=(tok_spec, cshard))
        args = (params_shape, batch_shape)
        state_bytes = _tree_bytes_per_device(params_shape, pspec, mesh)
        tokens = cell.global_batch * cell.seq_len
    else:  # decode
        io, caches_shape = configs.decode_inputs(cfg, cell)
        cspec = tuple(
            shd.cache_spec_tree(cfg, cs, mesh, cell.global_batch)
            for cs in caches_shape)
        cshard = shd.named(cspec, mesh)
        tok_spec = NamedSharding(
            mesh, P(shd._batch_axis(cell.global_batch, mesh)))
        step_fn = steps_lib.make_decode_step(cfg)
        jfn = jax.jit(
            step_fn,
            in_shardings=(pshard, tok_spec, cshard, tok_spec),
            out_shardings=(tok_spec, cshard),
            donate_argnums=(2,) if donate else ())
        args = (params_shape, io["tokens"], caches_shape, io["pos"])
        state_bytes = (
            _tree_bytes_per_device(params_shape, pspec, mesh) +
            _tree_bytes_per_device(caches_shape, cspec, mesh))
        tokens = cell.global_batch   # one token per sequence per step

    with _mesh_context(mesh):   # ambient mesh for _shard_hint specs
        lowered = jfn.lower(*args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        }
    except Exception:   # noqa: BLE001 - backend may not support it
        mem_info = {}

    coll = parse_collectives(compiled.as_text(), n_dev)
    mflops = steps_lib.model_flops(cfg, params_shape, cell.kind, tokens)
    params_bytes = _tree_bytes_per_device(params_shape, pspec, mesh)
    act_bytes = analytic_activation_bytes(cfg, cell, mesh)

    return {
        "arch": arch, "shape": shape, "variant": variant,
        "mesh": list(mesh.devices.shape), "axes": list(mesh.axis_names),
        "n_devices": int(n_dev),
        "kind": cell.kind, "tokens_per_step": tokens,
        "hlo_flops_per_device": cost.get("flops"),
        "hlo_bytes_per_device": cost.get("bytes accessed"),
        "cost_analysis_keys": sorted(cost)[:32],
        "memory_analysis": mem_info,
        "state_bytes_per_device_analytic": state_bytes,
        "params_bytes_per_device": params_bytes,
        "cache_bytes_per_device": max(state_bytes - params_bytes, 0)
        if cell.kind == "decode" else 0,
        "activation_bytes_per_device_analytic": act_bytes,
        "collectives": coll,
        "model_flops_global": mflops,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def cell_filename(arch: str, shape: str, mesh_name: str,
                  variant: str = "") -> str:
    v = ("__" + variant.replace("=", "").replace(",", "_")) if variant else ""
    return f"{arch}__{shape}__{mesh_name}{v}.json".replace("/", "_")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["pod1", "pod2",
                                                       "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-donate", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    meshes = []
    if args.mesh in ("pod1", "both"):
        meshes.append(("pod1", mesh_lib.make_production_mesh()))
    if args.mesh in ("pod2", "both"):
        meshes.append(("pod2",
                       mesh_lib.make_production_mesh(multi_pod=True)))

    if args.all:
        cells = [(a, s) for a, s, ok, _ in configs.all_cells() if ok]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape in cells:
        for mesh_name, mesh in meshes:
            fname = os.path.join(
                args.out, cell_filename(arch, shape, mesh_name,
                                        args.variant))
            if os.path.exists(fname) and not args.force:
                print(f"[skip] {fname} exists")
                continue
            print(f"[lower] {arch} x {shape} x {mesh_name} "
                  f"variant={args.variant!r} ...", flush=True)
            try:
                rec = lower_cell(arch, shape, mesh, variant=args.variant,
                                 donate=not args.no_donate)
                with open(fname, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"[ok] flops/dev={rec['hlo_flops_per_device']:.3e} "
                      f"coll={rec['collectives']['bytes_total']:.3e}B "
                      f"compile={rec['compile_s']}s", flush=True)
            except Exception as e:   # noqa: BLE001 - record and continue
                failures.append((arch, shape, mesh_name, str(e)))
                print(f"[FAIL] {arch} x {shape} x {mesh_name}: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f[:3], f[3][:200])
        raise SystemExit(1)
    print("\nAll requested cells lowered + compiled successfully.")


if __name__ == "__main__":
    main()
