"""A benchmark defined in a temporary directory, at a size a CPU test can
run: the program's reduced ``vit_edge`` (32 px, 4 layers, dim 96, 4
heads) through the ``xla`` backend.  The cell's files are new files
only; the harness, the family code and the metric readers are the
committed ones."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

GEOMETRY = {"image": 32, "patch": 8, "dim": 96, "heads": 4, "layers": 4,
            "mlp_hidden": 384, "n_classes": 10, "ln_eps": 1e-05}


def config(mode: str, limit: float, chips=(1,)) -> dict:
    return {
        "name": f"tiny_{mode}", "registry": "vit_edge", "full": False,
        "family": "vit", "mode": mode, "backend": "xla",
        "buckets": [1, 2, 4], "chips": list(chips),
        "arithmetic": "int8" if mode == "int8" else "bf16",
        "calib_images": 4, "geometry": GEOMETRY,
        "kernels": {"vita_layer": "vita_layer"},
        "correct": {"reference": "f32" if mode == "int8" else "f32_bf16dot",
                    "control": "int4" if mode == "int8" else "int8",
                    "limits": {"logit_err": limit}},
    }


def make_root(tmp: pathlib.Path, cells, metrics=("images_per_s",
                                                 "frame_ms", "setup_s",
                                                 "latency_p95_ms")):
    """Write BENCHMARK.json and the cells' files under ``tmp``.  ``cells``
    maps a cell name to (config dict, traffic dict, chips)."""
    (tmp / "vbench" / "configs").mkdir(parents=True)
    (tmp / "vbench" / "traffic").mkdir(parents=True)
    (tmp / "vbench" / "metrics").mkdir(parents=True)
    bench = {"configs": [], "workloads": [], "end_to_end": [],
             "per_layer": []}
    for name, (cfg, mix, chips) in cells.items():
        cfile = f"vbench/configs/{cfg['name']}.json"
        (tmp / cfile).write_text(json.dumps(cfg))
        tname = f"{name.replace('.', '_')}_mix"
        (tmp / "vbench" / "traffic" / f"{tname}.json").write_text(
            json.dumps(mix))
        if cfg["name"] not in [c["name"] for c in bench["configs"]]:
            bench["configs"].append({"name": cfg["name"], "file": cfile})
        bench["workloads"].append({"name": name, "config": cfg["name"],
                                   "traffic": tname, "chips": chips})
    for m in metrics:
        shutil.copy(REPO / "vbench" / "metrics" / f"{m}.py",
                    tmp / "vbench" / "metrics" / f"{m}.py")
        bench["end_to_end"].append({"name": m, "unit": "x",
                                    "better": "lower",
                                    "source": "host_clock"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def closed(clients=4, bank=8):
    return {"loop": "closed", "clients": clients, "sla_ms": None,
            "warm_s": 0.2, "bank": bank}


def open_loop(rate=200.0, bank=8):
    return {"loop": "open", "arrivals": "poisson", "rate_per_s": rate,
            "sla_ms": 100, "warm_s": 0.2, "bank": bank}


def on_cpu(monkeypatch):
    """Let the harness run on the CPU: no look for a chip, and peak rates
    for the CPU's device kind so that the readers can run."""
    import jax
    from vbench import harness, peaks
    kind = jax.devices()[0].device_kind
    monkeypatch.setattr(harness, "check_device",
                        lambda chips: jax.devices()[0])
    monkeypatch.setitem(peaks.PEAKS, kind, peaks.PEAKS["TPU v5 lite"])
