"""Find a cell's files by the names in ``BENCHMARK.json``. No JAX.

A cell names a configuration and a traffic mix; the configuration is
``configs/<config>.json``, the mix ``traffic/<traffic>.json``, the
mix's ``kind`` the module ``kinds/<kind>.py``, a per-layer metric the
module ``layer_metrics/<metric>.py``, the configuration's ``family``
the modules ``families/<family>.py`` and ``references/<family>.py``.
Nothing here, in ``run.py`` or in ``worker.py`` names one of them.
"""

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


class UnknownName(Exception):
    """A cell, configuration, mix, kind, metric, family or device
    kind that no file or entry defines."""


def _read_json(path, what):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise UnknownName(f"no {what}: {path} does not exist") from None


def benchmark(path=None):
    """``BENCHMARK.json`` of this checkout (a test names its own
    through ``YARDSTICK_BENCHMARK``)."""
    return _read_json(
        path or os.environ.get("YARDSTICK_BENCHMARK")
        or os.path.join(CHECKOUT, "BENCHMARK.json"),
        "BENCHMARK.json",
    )


def load_cell(name, bench=None, rehearse=None):
    """``(cell, config, traffic)`` of the cell ``name``.

    ``rehearse`` names a tiny configuration (``configs/<rehearse>
    .json``) that takes the configuration's place, with the sizes of
    its ``rehearsal`` group laid over the mix: the control flow of the
    cell at a size a CPU runs. Such a run is never ``correct``.
    """
    bench = bench or benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise UnknownName(
            f"no cell {name!r} in BENCHMARK.json (it has: "
            f"{', '.join(sorted(cells))})"
        )
    cell = cells[name]
    traffic = _read_json(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json"),
        f"traffic mix {cell['traffic']!r}",
    )
    config_name = rehearse or cell["config"]
    config = _read_json(
        os.path.join(HERE, "configs", config_name + ".json"),
        f"configuration {config_name!r}",
    )
    if rehearse:
        if "rehearsal" not in config:
            raise UnknownName(
                f"configuration {rehearse!r} has no rehearsal sizes"
            )
        traffic = {**traffic, **config["rehearsal"]}
    return cell, config, traffic


def _module(package, name, what):
    try:
        return importlib.import_module(f"yardstick.{package}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"yardstick.{package}.{name}":
            raise
        raise UnknownName(
            f"no {what} {name!r}: yardstick/{package}/{name}.py does "
            "not exist"
        ) from None


def kind_module(traffic):
    return _module("kinds", traffic["kind"], "job kind")


def family_module(config, package="families"):
    """What the yardstick knows of the configuration's family: under
    ``families`` its sizes, counts and program config (no JAX at
    import), under ``references`` its plain forward loss."""
    return _module(package, config["family"], "family")


def metric_module(name):
    """A per-layer metric's reader. A metric's module is named after
    it, with ``.`` and ``-`` (which a module name cannot hold) as
    ``_``."""
    return _module(
        "layer_metrics", name.replace(".", "_").replace("-", "_"),
        "per-layer metric",
    )


def metrics_of(cell_name, entries):
    """The entries of ``end_to_end`` or ``per_layer`` that the cell
    reports: those with no ``workloads`` key, or with the cell in it."""
    return [
        m for m in entries
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def peak_of(device_kind):
    table = _read_json(os.path.join(HERE, "peaks.json"), "peaks.json")
    if device_kind not in table or device_kind == "source":
        raise UnknownName(
            f"device kind {device_kind!r} is not in yardstick/"
            "peaks.json: a device without published peaks is an "
            "error, not a default"
        )
    return table[device_kind]
