"""A CPU-sized cell for the benchmark's tests: the files under ``data/``
added to a copy of the benchmark as a configuration, a traffic mix, the
cell's limits and the cell, found by name like any other."""
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELL = "tiny-moe.tiny-mix"


def bench_copy(tmp: Path) -> Path:
    """A checkout-like root holding ``BENCHMARK.json`` and the benchmark,
    with the tiny configuration, mix and cell added as files and entries."""
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(REPO / "benchmarks" / "chip", tmp / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(DATA / "tiny-moe.json",
                tmp / "benchmarks" / "chip" / "configs" / "tiny-moe.json")
    shutil.copy(DATA / "tiny-mix.json",
                tmp / "benchmarks" / "chip" / "traffic" / "tiny-mix.json")
    shutil.copy(DATA / "tiny-limits.json",
                tmp / "benchmarks" / "chip" / "limits" / f"{CELL}.json")
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-moe", "source": "test-only",
        "file": "benchmarks/chip/configs/tiny-moe.json", "reduced": [],
        "why": "CPU size"})
    bench["workloads"].append({"name": CELL, "config": "tiny-moe",
                               "traffic": "tiny-mix", "chips": 1,
                               "why": "CPU size"})
    # the tiny cell reports what the prefill cell reports
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "mixtral-8x7b.prefill" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
