"""Find a metric's reader by the metric's name."""

from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load_reader(kind: str, name: str):
    """The `read(record)` of layer_metrics/<name>.py or e2e_metrics/<name>.py,
    loaded by path so that a dotted metric name is fine."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
