"""A cell by name: its configuration, traffic mix and limits, all data.

``BENCHMARK.json`` lists the cells (``workloads``); a cell names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/mixes/<traffic>.json``), and its correctness limits live in
``bench/limits/<cell>.json``.  Nothing here is specific to one cell.

A configuration file holds the model builder of ``repro.models.cnn`` and
its arguments, the synthetic task and its size, the protocol row of
``repro.core.protocol.baseline_configs`` with its settings, the dtype and
matmul precision.  A mix holds the cohort (``clients``, ``cohort_size``),
the ``EngineConfig`` fields it sets (``engine``) and the fixed
``total_rounds`` that sets the protocol's schedules.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path.relative_to(ROOT)} is missing")
    return json.loads(path.read_text())


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def load(name: str) -> dict:
    """Everything the harness needs to run the cell ``name``."""
    cells = {w["name"]: w for w in benchmark()["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {', '.join(sorted(cells))})")
    w = cells[name]
    return assemble(name, w["config"], w["traffic"], w["chips"])


def assemble(name: str, config: str, traffic: str, chips: int = 1) -> dict:
    """The cell ``name`` of configuration ``config`` under mix ``traffic``
    from their files, with the metrics BENCHMARK.json gives it."""
    bm = benchmark()
    return {
        "name": name, "chips": chips,
        "config": _json(BENCH / "configs" / f"{config}.json"),
        "mix": _json(BENCH / "mixes" / f"{traffic}.json"),
        "limits": _json(BENCH / "limits" / f"{name}.json"),
        "end_to_end": [m for m in bm["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bm["per_layer"]
                      if name in m.get("workloads", [name])],
    }


# ------------------------------------------------------ the system under test

def protocol_config(cfg: dict, mix: dict, name: str):
    from repro.core.protocol import baseline_configs

    proto = dict(cfg["protocol"])
    row = proto.pop("row")
    rows = baseline_configs(total_rounds=mix["total_rounds"], **proto)
    return dataclasses.replace(rows[row], name=name)


def engine_config(mix: dict, telemetry: str):
    from repro.fl.engine import EngineConfig
    from repro.fl.sampling import SamplingConfig

    return EngineConfig(
        sampling=SamplingConfig(cohort_size=mix.get("cohort_size")),
        telemetry=telemetry, **mix["engine"])


def build_engine(cell: dict, splits: dict, engine_key, telemetry: str):
    """One ``FederatedEngine`` for the cell, on the benchmark's data."""
    from repro.data.federated import FederatedSplits
    from repro.fl.engine import FederatedEngine
    from repro.models import cnn

    cfg, mix = cell["config"], cell["mix"]
    if cfg["dtype"] != "float32":
        raise ValueError(f"dtype {cfg['dtype']!r}: the program trains in "
                         "float32 only")
    model = getattr(cnn, cfg["model"]["builder"])(**cfg["model"]["args"])
    return FederatedEngine(model, protocol_config(cfg, mix, cell["name"]),
                           FederatedSplits(**splits), engine_key,
                           engine_config(mix, telemetry))
