"""The benchmark is driven by data: cells, configurations, traffic mixes
and per-layer metrics are found by name, and a whole run goes through on
the CPU at a tiny size (with the chip check skipped)."""
import json
import subprocess
import sys
from types import SimpleNamespace

import numpy as np

from chipbench_tiny import CELL, REPO, bench_copy

sys.path.insert(0, str(REPO))
from benchmarks.chip import harness, spec  # noqa: E402
from benchmarks.chip import traffic as T  # noqa: E402

V5E = "TPU v5 lite"


def test_files_added_by_name_are_found(tmp_path):
    root = bench_copy(tmp_path)
    metric = root / "benchmarks/chip/metrics/tiny.transfers_per_s.py"
    metric.write_text("def read(ctx):\n"
                      "    return ctx.transfers / ctx.window_s\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "tiny.transfers_per_s", "unit": "1/s", "better": "higher",
        "source": "program_counter", "layer": "expert residency",
        "moves": "ttft_p50_s", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(root, CELL)
    assert (cell.dims.d_model, cell.dims.n_experts) == (64, 8)
    assert cell.traffic["clients"] == 2
    assert "tiny.transfers_per_s" in [m["name"] for m in cell.per_layer]
    read = spec.metric_reader(root, "tiny.transfers_per_s")
    assert read(SimpleNamespace(transfers=10, window_s=2.0)) == 5.0


def test_every_cell_and_metric_of_the_benchmark_loads():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(REPO, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in e2e
            assert callable(spec.metric_reader(REPO, m["name"]))


def test_traffic_is_the_same_for_one_seed():
    mix = spec.load_json(REPO / "benchmarks/chip/traffic/prefill.json")
    seed = 2 ** 33 + 5
    a, b = T.Traffic(mix, 32000, seed), T.Traffic(mix, 32000, seed)
    for c in range(mix["clients"]):
        for i in range(20):
            ra, rb = a.request(c, i), b.request(c, i)
            assert np.array_equal(ra.prompt, rb.prompt)
            assert ra.out_tokens == rb.out_tokens
    # another seed: the same lengths dealt to the same clients, other tokens
    other = T.Traffic(mix, 32000, -7)
    k, n = mix["clients"], mix["prompt_tokens"]["distinct"]

    def lengths(t):
        return [(len(r.prompt), r.out_tokens) for r in
                (t.request(c, i) for c in range(k) for i in range(n))]

    mine, theirs = lengths(a), lengths(other)
    assert mine == theirs
    # each client's first n/k requests together hold every length once
    first = [len(a.request(c, i).prompt) for c in range(k)
             for i in range(n // k)]
    assert sorted(first) == sorted(a.prompt_lengths)
    assert not np.array_equal(a.request(0, 0).prompt,
                              other.request(0, 0).prompt)
    q = mix["prompt_tokens"]["quantum"]
    assert all(n % q == 0 and 256 <= n <= 2048 for n in first)


def test_measurement_refuses_a_cpu(capsys):
    rc = harness.main(["--workload", "mixtral-8x7b.prefill", "--seed", "1",
                       "--seconds", "1"], root=REPO)
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_importing_the_benchmark_touches_no_backend():
    code = (
        "import sys; sys.path[:0] = [%r, %r]\n"
        "import benchmarks.chip.harness, benchmarks.chip.trace\n"
        "import benchmarks.chip.reference.moe_decoder\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, 'a backend was initialised'\n"
        % (str(REPO), str(REPO / "src")))
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})


def test_a_tiny_cell_runs_end_to_end(tmp_path, capsys):
    root = bench_copy(tmp_path)
    r = harness.run_cell(root, CELL, 2 ** 32 + 17, 1.5, False,
                         require_chip=False)
    assert r["correct"], r
    # the warm-up reached every shape the window used
    assert "window compiles: 0 traced," in capsys.readouterr().err
    assert {"ttft_p50_s", "setup_s", "hbm_peak_gib"} - {"hbm_peak_gib"} \
        <= set(r["metrics"])
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["max_logit_gap"]["value"] <= \
        r["checks"]["max_logit_gap"]["limit"]
    traced = harness.run_cell(root, CELL, 2 ** 32 + 17, 1.5, True,
                              require_chip=False, peaks=spec.peaks_of(V5E))
    assert traced["correct"]
    got = traced["metrics"]
    assert {"device.idle_share.prefill", "residency.hit_rate.prefill",
            "expert_ffn_roofline.prefill", "mfu.prefill"} <= set(got)
    assert 0 < got["expert_ffn_roofline.prefill"]["value"] <= 100
    assert 0 < traced["device"]["busy_s"] <= traced["device"]["window_s"]
    assert traced["breakdown"]["device_ops"]
