"""Readings that the limit of ``check.py`` is set from, for one cell, and
the proof that the limit separates them.

In one process (set-up once): for each seed, the cell's own window
(``harness.run_window``) and sample (``harness.served_sample``), then the
comparison of the served sample with the float32 reference (the program's
reading) and of the float8 control put in the program's place, judged by
the same rule and limits (the control's reading). The benchmark's own runs
do not run the control.

  python3 benchmarks/chip/calibrate.py --workload mixtral-8x7b.prefill \
      --seconds 51 --seeds 11,12,13,...

Prints one JSON line per seed and a summary line last. Exits 1 unless the
program is correct and the control not correct on every seed.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import check, harness, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma list")
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    limits = check.load_limits(spec.bench_dir(ROOT), cell.name)
    su = harness.set_up(ROOT, cell)
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        win = harness.run_window(su, seed, args.seconds)
        served = harness.served_sample(win, su.mix, seed)
        cmp = check.compare(su.dims, served, limits, control=True)
        row = {"seed": seed, "program": cmp.readings(),
               "control": cmp.control.readings(),
               "seconds": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows),
               "limit": limits["max_logit_gap"],
               "program_correct": sum(r["program"]["correct"] for r in rows),
               "control_correct": sum(r["control"]["correct"] for r in rows)}
    for k in ("max_logit_gap", "max_logit_gap_all", "mean_logit_gap",
              "mismatch_share"):
        lower = max(r["program"][k] for r in rows)
        upper = min(r["control"][k] for r in rows)
        summary[k] = {"lower": lower, "upper": upper,
                      "ratio": upper / lower if lower else None}
    print(json.dumps(summary))
    ok = (summary["program_correct"] == len(rows)
          and summary["control_correct"] == 0)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
