"""Finds the benchmark's data files by name.

``BENCHMARK.json`` names cells, configurations, traffic mixes and per-layer
metrics; each name is a file:

    configs/<config>.json         sizes, the entry point's flags, the reference
    traffic/<traffic>.json        size, batch, loader and layout flags
    layer_metrics/<metric>.json   the reader kind and its parameters
    readers/<kind>.py             ``read(record, **params)``
    references/<module>.py        ``cells(params, sizes, tally)``

Optional, each read where it is there and passed over where it is not:

    ``size`` in a traffic file: one sample's size (an image's side, a
        sequence's length), the key under which ``model_flops_per_img`` is read
    ``model_flops_per_img`` in a configuration: ``{"<size>": FLOPs a sample}``,
        held against the reference's count in every run
    ``batch_spec(sizes, traffic)`` in a reference: the ``ShapeDtypeStruct``s of
        ``(x, y)``; absent, one square float32 RGB image and one class a sample

A key of a configuration's ``sizes`` that the parsed flags carry too
(``num_layers``) is held equal to the flag by the tests; any other is the
reference's alone.

A later PR adds a cell, a configuration, a metric or a reader kind by adding
files and entries; no file that is here needs an edit for it.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_json(path: str) -> Any:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with the files it names."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    bench_dir: str

    @property
    def family(self) -> str:
        return self.traffic["family"]

    def argv(self, seed: int) -> List[str]:
        """Flags for the entry point's parser: the configuration's, then the
        traffic's, then the seed.  The driver's seeds pass 2**31, which
        ``jax.random.key`` under 32-bit integers does not take."""
        return [*self.config["argv"], *self.traffic["argv"],
                "--seed", str(int(seed) % (2**31 - 1))]

    @property
    def size(self) -> Optional[int]:
        """The size of one sample as the traffic states it, or None."""
        return self.traffic.get("size")

    def reference(self):
        """The configuration's plain reference, as a module."""
        path = os.path.join(self.bench_dir, "references",
                            self.config["reference"] + ".py")
        return _load_module(
            path, "perfbench_reference_" + self.config["reference"])

    def reference_cells(self) -> Callable:
        """``cells(params, sizes, tally)`` of the configuration's plain
        reference: one float32 function per cell of the program's model."""
        return self.reference().cells

    def batch_spec(self):
        """The ``ShapeDtypeStruct``s of one batch ``(x, y)``: the reference's
        own ``batch_spec(sizes, traffic)``, else an image and its class."""
        from perfbench.references import plain

        spec = getattr(self.reference(), "batch_spec", plain.image_batch_spec)
        return spec(self.config["sizes"], self.traffic)

    def stored_model_flops(self) -> Optional[int]:
        """``model_flops_per_img`` of the configuration at the traffic's
        size, or None where either file does not state it."""
        return self.config.get("model_flops_per_img", {}).get(str(self.size))


class Catalog:
    """``BENCHMARK.json`` of a checkout and the directory of data files."""

    def __init__(self, root: str = ROOT, bench_dir: Optional[str] = None):
        self.root = root
        self.bench = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = bench_dir or os.path.join(root, self.bench["paths"][0])

    def cell(self, name: str) -> Cell:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                break
        else:
            known = ", ".join(w["name"] for w in self.bench["workloads"])
            raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
        for c in self.bench["configs"]:
            if c["name"] == w["config"]:
                config = _load_json(os.path.join(self.root, c["file"]))
                break
        else:
            raise KeyError(f"workload {name!r} names no listed configuration")
        traffic = _load_json(os.path.join(
            self.bench_dir, "traffic", w["traffic"] + ".json"))
        return Cell(name, int(w["chips"]), w["config"], config, traffic,
                    self.bench_dir)

    def metrics(self, group: str, cell: str) -> List[Dict[str, Any]]:
        """Entries of ``end_to_end`` or ``per_layer`` that ``cell`` reports."""
        return [m for m in self.bench[group]
                if "workloads" not in m or cell in m["workloads"]]

    def read_layer_metric(self, name: str, record: Dict[str, Any]
                          ) -> Optional[float]:
        """The metric's reader on ``record``; None where it finds nothing."""
        spec = _load_json(os.path.join(
            self.bench_dir, "layer_metrics", name + ".json"))
        reader = _load_module(
            os.path.join(self.bench_dir, "readers", spec["reader"] + ".py"),
            "perfbench_reader_" + spec["reader"])
        return reader.read(record, **spec.get("params", {}))

    def peak(self, device_kind: str, what: str) -> float:
        """A published peak of one chip; a kind the table lacks is an error,
        never a default."""
        table = _load_json(os.path.join(self.bench_dir, "peaks.json"))
        for row in table["chips"]:
            if row["device_kind"] == device_kind:
                return float(row[what])
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json; add the "
            "chip with the source of its numbers")
