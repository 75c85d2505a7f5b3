"""Trace reduction on a small trace recorded on the CPU (committed beside
this file: a jitted matmul run three times inside ``bench.window``, each
under ``bench.poll``, 20 ms apart), the peaks table, and the FLOP
arithmetic."""
import sys

import pytest

from chipbench_tiny import DATA, REPO

sys.path.insert(0, str(REPO))
from benchmarks.chip import flops, spec, trace  # noqa: E402

TRACE = DATA / "cpu_window.xplane.pb"


def test_reduction_of_a_recorded_trace():
    s = trace.reduce(str(TRACE))
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.061914139)
    assert s.busy_s == pytest.approx(0.000385097)
    assert 0.99 < s.idle_share < 1.0
    assert set(s.programs) == {"_lambda"}
    assert s.programs["_lambda"] == pytest.approx(s.busy_s)
    # the three sleeps between polls are the longest idle stretches
    assert [round(sec, 2) for _, sec in s.gaps[:3]] == [0.02, 0.02, 0.02]
    assert s.gaps[0][0] == "host: outside any span"
    assert s.gaps[3][0] == "bench.poll"
    assert s.device_ops() == [["_lambda", s.programs["_lambda"]]]


def test_engine_spans_name_the_gaps():
    s = trace.reduce(str(TRACE))
    full = trace.reduce(str(TRACE), host_spans=[("decode.step", 0, 10 ** 18)])
    assert full.gaps[0][0] == "decode.step"
    assert full.busy_s == s.busy_s


def test_union_and_program_names():
    assert trace.union_ns([(5, 9), (0, 3), (2, 4), (9, 12)]) == [(0, 4),
                                                                 (5, 12)]
    assert trace.program_name("jit_grouped_raw(17)") == "grouped_raw"
    assert trace.program_name("expert_apply") == "expert_apply"


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks_of("TPU v9 imaginary")
    v5e = spec.peaks_of("TPU v5 lite")
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"]) == (197e12,
                                                                  819e9)


def test_flop_arithmetic_at_published_widths():
    mix = spec.dims_of("mixtral-8x7b", spec.load_json(
        REPO / "benchmarks/chip/configs/mixtral-8x7b.json"))
    assert flops.expert_bytes(mix) == 3 * 4096 * 14336 * 2   # 352 MB
    assert flops.expert_flops_per_row(mix) == 6 * 4096 * 14336
    # attention projections 42 M, router 32 K, two experts 352 M
    assert flops.layer_active_params(mix) == (
        (2 * 32 + 2 * 8) * 128 * 4096 + 4096 * 8 + 2 * 3 * 4096 * 14336)
    assert flops.token_flops(mix, 0, head=True) - flops.token_flops(
        mix, 0, head=False) == 2 * 32000 * 4096
