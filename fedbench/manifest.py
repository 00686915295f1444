"""Finding the benchmark's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one cell, one cohort
distribution, one FLOP count or one layer metric is a file of its own::

    fedbench/configs/<config>.json        sizes, source, builder, input spec
    fedbench/references/<config>.py       make_loss(config) -> loss(params, x, y, mask)
    fedbench/flops/<config>.py            required(config, job) -> FLOPs, bytes
    fedbench/inputs/<kind>.py             make(spec, ...) -> {"x", "y"}
    fedbench/workloads/<cell>.json        cohort, batch, epochs, waves, chips
    fedbench/cohorts/<kind>.py            sizes(spec, n_clients, rng) -> [C]
    fedbench/layer_metrics/<metric>.py    LAYER, UNIT, MOVES, SOURCE, read(...)
    fedbench/peaks.json                   device peaks by exact device_kind

A configuration or a workload file may hold an ``engine`` block: further
arguments of ``FedSim`` (``trainable``, ``optimizer``, ``aggregator``,
...), resolved as a builder's are. A configuration file may hold a
``scopes`` block: names of its own model for the reduction of a trace.

A later PR adds a cell, a configuration or a layer metric by adding
files and ``BENCHMARK.json`` entries; nothing here lists them. Every
function takes the checkout's root, so a test can point the same code
at a copy of the tree.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Any, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = "fedbench"


def _read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}; it has "
                   f"{[e['name'] for e in entries]}")


def cell_entry(manifest: dict, cell: str) -> dict:
    return _entry(manifest["workloads"], cell, "workload")


def load_workload(root: str, cell: str) -> dict:
    return _read_json(
        os.path.join(root, BENCH_DIR, "workloads", f"{cell}.json"))


def load_config(root: str, manifest: dict, config: str) -> dict:
    entry = _entry(manifest["configs"], config, "configuration")
    return _read_json(os.path.join(root, entry["file"]))


def load_module(root: str, kind: str, name: str):
    """The module ``fedbench/<kind>/<name>.py`` of the tree at ``root``,
    loaded by path: a new file needs no ``__init__`` entry."""
    path = os.path.join(root, BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"fedbench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_for(entries: list, cell: str) -> list:
    """The metric entries read in ``cell``: those with no ``workloads``
    key, and those that list it."""
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def load_peaks(root: str, device_kind: str) -> dict:
    """Peaks of one chip, by the exact ``device_kind`` JAX reports. A
    device the table does not hold is an error, never a default."""
    table = _read_json(os.path.join(root, BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(
            f"fedbench/peaks.json has no device_kind {device_kind!r} "
            f"(it has {sorted(table['devices'])}); add it with its source")
    return table["devices"][device_kind]


def load_op_categories(root: str) -> dict:
    """``{category: [words]}``: how a device op's ``hlo_category`` puts
    it in a kernel category (``fedbench/op_categories.json``)."""
    return _read_json(
        os.path.join(root, BENCH_DIR, "op_categories.json"))["rules"]


def load_trace_names(root: str, config: Optional[dict] = None) -> dict:
    """The program's names the reduction of a trace reads
    (``fedbench/trace_names.json``), with the ``scopes`` block of
    ``config`` laid over them: its ``parts`` are added, its ``blocks``
    pattern is one more alternative."""
    names = _read_json(os.path.join(root, BENCH_DIR, "trace_names.json"))
    own = (config or {}).get("scopes", {})
    names["parts"] = names["parts"] + own.get("parts", [])
    if "blocks" in own:
        names["blocks"] = f"{names['blocks']}|{own['blocks']}"
    return names


def resolve(spec: Any, config: dict) -> Any:
    """Turn a JSON argument into the Python value a builder takes:
    ``{"$key": "hidden_size"}`` is that top-level size of ``config`` (so
    the builder cannot drift from the published sizes beside it),
    ``{"$dtype": "bfloat16"}`` is the ``jax.numpy`` type,
    ``{"$ref": "pkg.mod:name"}`` is that object itself (a predicate such
    as ``trainable``) and ``{"$call": "pkg.mod:name", "kwargs": {...}}``
    is that callable's result, arguments resolved the same way. Anything
    else is itself."""
    if isinstance(spec, dict):
        if "$key" in spec:
            return config[spec["$key"]]
        if "$ref" in spec:
            return by_path(spec["$ref"])
        if "$dtype" in spec:
            import jax.numpy as jnp

            return jnp.dtype(spec["$dtype"])
        if "$call" in spec:
            kwargs = resolve(spec.get("kwargs", {}), config)
            return by_path(spec["$call"])(**kwargs)
        return {k: resolve(v, config) for k, v in spec.items()}
    if isinstance(spec, list):
        return [resolve(v, config) for v in spec]
    return spec


def by_path(dotted: str):
    """``"pkg.mod:name"`` -> the object."""
    module, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(module), attr)


def sized(config: dict, tiny: bool) -> dict:
    """The configuration as it is run: the file's content, in a
    rehearsal with its ``tiny.sizes`` laid over the published sizes, so
    that the program's builder and the plain reference read one set."""
    return dict(config, **config["tiny"].get("sizes", {})) if tiny else config


def build_model(config: dict, tiny: bool):
    """The configuration's model through the program's own builder, at
    the sizes of ``sized(config, tiny)``; a rehearsal may also name
    another builder and arguments (``tiny.path``, ``tiny.kwargs``)."""
    builder = config["builder"]
    path, kwargs = builder["path"], dict(builder["kwargs"])
    if tiny:
        path = config["tiny"].get("path", path)
        kwargs.update(config["tiny"].get("kwargs", {}))
    return by_path(path)(**resolve(kwargs, sized(config, tiny)))


def engine_args(config: dict, workload: dict) -> dict:
    """``FedSim``'s arguments beyond ``batch_size``, ``learning_rate``
    and ``mesh``: the configuration's ``engine`` block with the
    workload's laid over it, resolved. Empty where neither has one."""
    block = dict(config.get("engine", {}))
    block.update(workload.get("engine", {}))
    return resolve(block, config)


def input_spec(config: dict, tiny: bool) -> dict:
    """The configuration's input spec, with the CPU-test sizes laid over
    it when ``tiny``."""
    spec = dict(config["input"])
    if tiny:
        spec.update(config["tiny"].get("input", {}))
    return resolve(spec, sized(config, tiny))
