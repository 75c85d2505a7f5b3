"""The plain float32 reference against the served path at a CPU size:
chunked-prefill logits, and greedy tokens served through the frontend at
batch 2, prefill then decode. The float8 control, put in the program's
place and judged by the same rule and limits, is not correct."""
import sys

import jax
import numpy as np
import pytest

from chipbench_tiny import DATA, REPO

sys.path.insert(0, str(REPO))
from benchmarks.chip import check, spec, traffic as T  # noqa: E402
from benchmarks.chip import weights as W  # noqa: E402
from benchmarks.chip.reference import moe_decoder as R  # noqa: E402


@pytest.fixture(scope="module")
def served_engine():
    from repro.core.cache import HostExpertStore
    from repro.models.model import init_without_experts
    from repro.serving.batching import BatchedServingEngine
    from repro.serving.frontend import ServingFrontend

    dims = spec.dims_of("tiny-moe", spec.load_json(DATA / "tiny-moe.json"))
    cfg = spec.arch_config(dims)
    expected = jax.eval_shape(init_without_experts(cfg), jax.random.PRNGKey(0))
    params = W.program_params(dims, W.dense(dims), expected)
    store = HostExpertStore(W.host_experts(dims))
    eng = BatchedServingEngine(cfg, params, policy="odf", store=store,
                               max_batch=2, max_seq=128, prefill_budget=16,
                               cache_capacity=8, temperature=0.0)
    return dims, eng, ServingFrontend(eng)


def test_chunked_prefill_logits_match_the_reference(served_engine):
    dims, eng, _ = served_engine
    prompt = T.fixed_prompt(dims.vocab, 40, 3)        # chunks of 16, 16, 8
    logits, *_ = eng.prefill_layers(prompt[None])
    got = np.asarray(logits, np.float32)[0, :dims.vocab]
    h = R.final_hidden(dims, [prompt])[0][0][-1:]
    dw = W.dense(dims)
    want = np.asarray(R._head(h, dw["ln_f"], dw["embed"], dims, None))[0]
    # bfloat16 activations through 2 layers against float32: logits of
    # standard deviation about 1 agree to a few hundredths
    assert np.max(np.abs(got - want)) < 0.1
    assert np.argmax(got) == np.argmax(want)


def test_served_tokens_agree_and_the_control_does_not(served_engine):
    from repro.serving.api import GenerationRequest, SamplingParams

    dims, _, fe = served_engine
    prompts = [T.fixed_prompt(dims.vocab, n, 10 + n) for n in (40, 24)]
    handles = [fe.submit(GenerationRequest(
        prompt=p, params=SamplingParams(max_new_tokens=12, temperature=0.0)))
        for p in prompts]
    fe.drain()
    served = [(p, list(h.tokens)) for p, h in zip(prompts, handles)]
    assert all(len(t) == 13 for _, t in served)
    limits = spec.load_json(DATA / "tiny-limits.json")
    cmp = check.compare(dims, served, limits, control=True)
    assert cmp.tokens == 26
    assert cmp.correct, cmp.readings()
    assert cmp.control.tokens == 26
    assert cmp.control.correct is False, cmp.control.readings()
    assert cmp.control.max_logit_gap > 3 * max(cmp.max_logit_gap, 0.01)
