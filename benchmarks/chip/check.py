"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, the served
requests of a sample are run through the plain reference
(``reference/moe_decoder.py``): each prompt followed by the tokens the
program served. At every position where the program chose a token, the
number compared is how far that token's reference logit lies below the
reference's best logit there; the run's number is the widest such gap. The
traffic is greedy (temperature 0), so a served token is the program's own
first choice and a sound program leaves only rounding near ties.

The control is the reference computed in float8 (``quant="fp8"``), put in
the program's place: at the same positions of the same prompts and tokens,
the token the float8 model puts first is judged exactly as a served token
is, by the same rule and the same limits (``Comparison.control``). A limit
that the control does not fail separates nothing.

Positions whose routing the reference itself decides within rounding are
left out, by a rule on the reference alone: where, in some layer, the
k-th largest router logit of the position exceeds the (k+1)-th by less
than ``router_margin_floor``, bfloat16 rounding of the router's input can
swap an expert, and with random weights one swapped expert moves that
position's logits by whole units (``PERF.md`` gives the readings).

Each cell's limits live in ``limits/<cell>.json``, with the readings they
were set from; a cell without one has no limit and cannot be checked.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class Comparison:
    requests: int
    tokens: int                  # positions compared
    max_logit_gap: float         # widest gap over the positions kept
    left_out: int                # positions within rounding of a routing tie
    max_logit_gap_all: float     # widest gap, none left out
    mean_logit_gap: float
    mismatch_share: float        # chosen tokens not the reference's first
    limits: dict = dataclasses.field(repr=False)
    control: Optional["Comparison"] = dataclasses.field(default=None,
                                                        repr=False)

    def checks(self) -> dict:
        return {"max_logit_gap": {"value": self.max_logit_gap,
                                  "limit": self.limits["max_logit_gap"]}}

    @property
    def correct(self) -> bool:
        return (self.tokens > self.left_out
                and self.max_logit_gap <= self.limits["max_logit_gap"])

    def readings(self) -> dict:
        out = {k: v for k, v in dataclasses.asdict(self).items()
               if k not in ("limits", "control")}
        out["correct"] = self.correct
        return out


def judge(requests: int, gaps: np.ndarray, margins: np.ndarray,
          limits: dict) -> Comparison:
    """The reading of chosen tokens' ``gaps`` at positions whose smallest
    router ``margins`` are known, under ``limits``."""
    keep = margins >= limits["router_margin_floor"]
    return Comparison(
        requests=requests, tokens=int(gaps.size),
        max_logit_gap=float(np.max(gaps[keep])) if keep.any()
        else float("inf"),
        left_out=int((~keep).sum()),
        max_logit_gap_all=float(np.max(gaps)) if gaps.size else float("nan"),
        mean_logit_gap=float(np.mean(gaps)) if gaps.size else float("nan"),
        mismatch_share=float(np.mean(gaps > 0)) if gaps.size
        else float("nan"),
        limits=limits)


def sample(candidates: Sequence[Tuple[np.ndarray, List[int]]], n: int,
           seed: int) -> List[Tuple[np.ndarray, List[int]]]:
    """Up to ``n`` served requests drawn from the seed, the longest
    (prompt and served tokens) always among them."""
    cands = [c for c in candidates if len(c[1]) > 0]
    if len(cands) <= n:
        return cands
    longest = max(range(len(cands)),
                  key=lambda i: len(cands[i][0]) + len(cands[i][1]))
    rest = [i for i in range(len(cands)) if i != longest]
    rng = np.random.default_rng([int(seed) & ((1 << 64) - 1), 3])
    pick = [longest] + sorted(rng.choice(rest, n - 1, replace=False).tolist())
    return [cands[i] for i in pick]


def load_limits(bench_dir: Path, cell: str) -> dict:
    with open(bench_dir / "limits" / f"{cell}.json") as f:
        return json.load(f)


def compare(dims, served: Sequence[Tuple[np.ndarray, List[int]]],
            limits: dict, control: bool = False) -> Comparison:
    """The served tokens of ``served`` = [(prompt, served tokens)] judged
    against the reference under the cell's ``limits``; with ``control``,
    the float8 control judged alike at the same positions."""
    import jax.numpy as jnp

    from benchmarks.chip.reference import moe_decoder as R

    if not served:
        empty = np.zeros(0, np.float32)
        return judge(0, empty, empty, limits)
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(t[:-1], np.int32)])
            for p, t in served]
    pos = [np.arange(len(p) - 1, len(p) - 1 + len(t)) for p, t in served]
    tokens = np.concatenate([np.asarray(t, np.int32) for _, t in served])

    def rows_of(hidden):
        return (jnp.concatenate([h[jnp.asarray(p)]
                                 for (h, _), p in zip(hidden, pos)]),
                np.concatenate([np.asarray(m)[p]
                                for (_, m), p in zip(hidden, pos)]))

    rows, margins = rows_of(R.final_hidden(dims, seqs))
    ctrl_rows = rows_of(R.final_hidden(dims, seqs, "fp8"))[0] if control \
        else None
    gaps, ctrl = R.served_gaps(dims, rows, tokens, ctrl_rows)
    out = judge(len(served), gaps, margins, limits)
    if ctrl is not None:
        out.control = judge(len(served), ctrl, margins, limits)
    return out
