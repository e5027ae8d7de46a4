"""A copy of the benchmark with toy configurations, for tests on the CPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TOY_CONFIGS = {
    "toy-powerlaw": {"generator": "power_law_gcn", "pattern_seed": 0, "num_nodes": 510,
                     "num_edges": 4000, "num_features": 16, "num_classes": 5,
                     "zipf_alpha": 0.7, "n_cols": 16, "dtype": "float32",
                     "matmul_precision": "default"},
    "toy-powerlaw-4chips": {"generator": "power_law_gcn", "pattern_seed": 0, "num_nodes": 510,
                            "num_rows": 512, "num_edges": 4000, "num_features": 16,
                            "num_classes": 5, "zipf_alpha": 0.7, "n_cols": 16,
                            "dtype": "float32"},
    "toy-delaunay": {"generator": "delaunay_share", "pattern_seed": 0, "num_rows": 1024,
                     "n_cols": 16, "dtype": "float32"},
}
# each toy cell runs the traffic of a cell of the benchmark ("twin"), on a
# toy operand, with the twin's limits, and reports the twin's metrics; the
# four-chip one, whose twin's limits are in chipbench/workloads/ while the
# cell itself is not in BENCHMARK.json, takes the metrics of arxiv-spmm-p1
# and the exchange's own
TOY_CELLS = [
    {"name": "toy-spmm-p1", "config": "toy-powerlaw", "twin": "arxiv-spmm-p1"},
    {"name": "toy-spmm-p4", "config": "toy-powerlaw-4chips", "twin": "arxiv-spmm-p4"},
    {"name": "toy-mesh-p1", "config": "toy-delaunay", "twin": "del24-spmm-p1"},
    {"name": "toy-gcn-p1", "config": "toy-powerlaw", "twin": "arxiv-gcn-p1"},
]
EXCHANGE_METRICS = [
    {"name": "exchange_rows", "unit": "rows", "better": "lower", "source": "program_counter",
     "layer": "planner and schedules", "moves": "spmm_ms"},
    {"name": "collective_ms", "unit": "ms", "better": "lower", "source": "device_trace",
     "layer": "executor exchange", "moves": "spmm_ms"},
]


def make_toy_bench(dest: Path) -> Path:
    """Copy ``BENCHMARK.json`` and ``chipbench/`` (without run-time output)
    to ``dest`` and add the toy configurations and cells as files and
    entries alone; returns ``dest``."""
    dest = Path(dest)
    shutil.copytree(ROOT / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns(".operands", ".traces", ".jax_cache",
                                                  "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, cfg in TOY_CONFIGS.items():
        path = dest / "chipbench" / "configs" / f"{name}.json"
        path.write_text(json.dumps(cfg))
        spec["configs"].append({"name": name, "source": "toy", "file": str(
            path.relative_to(dest)), "reduced": [], "why": "toy"})
    cells = {c["name"]: c for c in spec["workloads"]}
    cells.setdefault("arxiv-spmm-p4", dict(cells["arxiv-spmm-p1"], name="arxiv-spmm-p4",
                                           chips=4))
    metrics = spec["end_to_end"] + spec["per_layer"]
    for m in EXCHANGE_METRICS:
        if m["name"] not in {e["name"] for e in metrics}:
            spec["per_layer"].append(dict(m, workloads=[]))
    work_dir = dest / "chipbench" / "workloads"
    for toy in TOY_CELLS:
        twin = cells[toy["twin"]]
        (work_dir / f"{toy['name']}.json").write_text(
            (work_dir / f"{twin['name']}.json").read_text())
        spec["workloads"].append(dict(twin, name=toy["name"], config=toy["config"]))
        reports = {twin["name"]} | ({"arxiv-spmm-p1"} if twin["chips"] == 4 else set())
        for m in spec["end_to_end"] + spec["per_layer"]:
            if reports & set(m.get("workloads", [])) or (
                    twin["chips"] == 4 and m["name"] in ("exchange_rows", "collective_ms")):
                m["workloads"].append(toy["name"])
    (dest / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dest
