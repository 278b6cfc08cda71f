"""Find a cell and everything it names, by name, from ``BENCHMARK.json``.

A cell is one entry of ``workloads``.  Its configuration is the JSON file
that the ``configs`` entry names, its traffic mix is
``vbench/traffic/<traffic>.json``, and each metric is read by
``vbench/metrics/<metric>.py``, a module with one function
``read(run) -> float | None``.  Adding a cell, a configuration, a mix or
a metric therefore adds files and entries and edits none.

What depends on the model family lives in two modules of the family,
found by the configuration's ``family`` (``family_module``), so that a
family joins by files alone:

``vbench/reference/<family>.py``, the plain reference, which imports
nothing of the program:

* ``init_params(key, geometry)``: the weights from a PRNG key, made on
  the device in one jitted call, in the pytree layout the program reads;
* ``forward(params, images, geometry, *, precision, act_scales)``:
  logits (B, n_classes) as a NumPy array, computed in ``precision``
  (the configuration's ``correct.reference`` or ``correct.control``);
* ``QUANT_MAX``: the fake-quantized precisions it computes, each with
  its largest quantized magnitude, and ``calibrate(params, images,
  geometry, precision)``, their activation scales (called only for a
  precision in ``QUANT_MAX``);
* ``program_geometry(cfg)``: the sizes of the program's model config
  ``cfg`` under the keys of the configuration's ``geometry``; the
  harness compares each with the configuration's, sequences as
  sequences.

``vbench/work/<family>.py``, operations and bytes from the shapes:

* ``model_ops_per_image(geometry)``: the operations of one image's
  forward;
* ``layer_calls(geometry, batch, weight_bytes)``: (operations, least
  bytes) of each encoder-layer kernel call of one forward on ``batch``
  images, one item per call.

The harness itself reads only ``image`` and ``n_classes`` of a
``geometry``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional


class SpecError(ValueError):
    """BENCHMARK.json, or a file it names, is missing or inconsistent."""


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    kind: str                         # "end_to_end" | "per_layer"
    read: Callable[[Any], Optional[float]]
    moves: Optional[str] = None
    layer: Optional[str] = None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]
    root: pathlib.Path


def _load_json(path: pathlib.Path, what: str) -> Dict[str, Any]:
    if not path.is_file():
        raise SpecError(f"{what}: no file {path}")
    with open(path) as f:
        return json.load(f)


def load_reader(root: pathlib.Path, metric: str) -> Callable:
    """``read`` of ``vbench/metrics/<metric>.py`` under ``root``."""
    path = root / "vbench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"metric {metric!r}: no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"vbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"metric {metric!r}: {path} defines no read(run)")
    return mod.read


def _applies(entry: Dict[str, Any], cell: str) -> bool:
    names = entry.get("workloads")
    return names is None or cell in names


def load_cell(root, workload: str) -> Cell:
    """Resolve ``workload`` against ``<root>/BENCHMARK.json``."""
    root = pathlib.Path(root)
    bench = _load_json(root / "BENCHMARK.json", "benchmark")
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; "
                        f"known: {', '.join(sorted(cells))}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"{workload}: unknown config {w['config']!r}")
    config = _load_json(root / configs[w["config"]]["file"],
                        f"config {w['config']}")
    traffic = _load_json(root / "vbench" / "traffic" / f"{w['traffic']}.json",
                         f"traffic {w['traffic']}")
    if int(w["chips"]) not in config.get("chips", [1]):
        raise SpecError(f"{workload}: config {w['config']} does not run "
                        f"on {w['chips']} chip(s)")

    def metrics(kind):
        out = []
        for m in bench.get(kind, []):
            if _applies(m, workload):
                out.append(Metric(
                    name=m["name"], unit=m["unit"], better=m["better"],
                    source=m["source"], kind=kind,
                    read=load_reader(root, m["name"]),
                    moves=m.get("moves"), layer=m.get("layer")))
        return out

    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=metrics("end_to_end"),
                per_layer=metrics("per_layer"), root=root)


def family_module(kind: str, config: Dict[str, Any]):
    """``vbench/<kind>/<family>.py`` for the config's model family
    (``kind`` is ``work`` or ``reference``)."""
    family = config["family"]
    return importlib.import_module(f"vbench.{kind}.{family}")
