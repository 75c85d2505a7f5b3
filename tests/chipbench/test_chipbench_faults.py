"""A run with the timed path broken underneath comes out not correct:
a token altered where the engine samples it, and expert weights that never
reach the residency pools (every host->HBM write dropped)."""
import sys

import pytest

from chipbench_tiny import CELL, REPO, bench_copy

sys.path.insert(0, str(REPO))
from benchmarks.chip import harness  # noqa: E402


def _alter_tokens(monkeypatch):
    from repro.serving.engine import EngineCore
    sample = EngineCore.sample_row

    def altered(lg, temperature, rng):
        return (sample(lg, temperature, rng) + 1) % 512

    monkeypatch.setattr(EngineCore, "sample_row", staticmethod(altered))


def _drop_pool_writes(monkeypatch):
    from repro.core import cache
    monkeypatch.setattr(cache, "_pool_write", lambda pool, slot, slab: pool)


@pytest.mark.parametrize("fault", [_alter_tokens, _drop_pool_writes],
                         ids=["token_altered", "pool_writes_dropped"])
def test_a_broken_path_is_not_correct(tmp_path, monkeypatch, fault):
    root = bench_copy(tmp_path)
    fault(monkeypatch)
    r = harness.run_cell(root, CELL, 99, 1.5, False, require_chip=False)
    assert r["correct"] is False
    gap = r["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
