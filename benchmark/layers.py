"""Per-layer metrics are files: ``layer_metrics/<metric name>.py``, each
with ``LAYER``, ``UNIT``, ``MOVES``, ``SOURCE`` and ``read(ctx)``, found by
the name ``BENCHMARK.json`` gives the metric. A reader that finds nothing
to read returns None and the metric is left out of the line."""

from __future__ import annotations

import importlib.util
import os

DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "layer_metrics")


def load_reader(name: str):
    path = os.path.join(DIR, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"per-layer metric {name!r} has no reader at {path}"
        )
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
