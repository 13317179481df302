"""The float32 references against the program at smoke widths on the
CPU, through the engine's own prefill and decode; and the float8
control, which must fail where the program passes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check
from chipbench.client import Client, Req
from chipbench.tests import smoke
from chipbench.weights import make_weights

#: smoke-size limits between the program's widest gaps (bf16 on the
#: CPU, seeds 3-10: dense under 0.003, ssm 0.007-0.23) and the float8
#: control's (dense 0.04 and more, ssm 0.43-1.15); the dense smoke
#: model's logits are small (d_model 64, embedding std 0.02)
SMOKE_GAP = {"dense": 0.01, "ssm": 0.3}
REF = {"dense": "qwen3", "ssm": "xlstm"}


def build(family, seed):
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    from repro.models.layers import PT
    from repro.serving import ServeEngine
    m = smoke.MODELS[family]
    model = build_model(ModelConfig(**m))
    params = make_weights(model.templates, seed, lambda x: isinstance(x, PT))
    engine = ServeEngine(model, params, **smoke.ENGINES[family])
    return m, model, params, engine


def serve(engine, prompts, max_new):
    drv = Client(engine, traced=False)
    drv.begin()
    reqs = [Req(rid=i, prompt=p, max_new=max_new, arrival=0.0)
            for i, p in enumerate(prompts)]
    for r in reqs:
        drv.add(r)
    queue = list(reqs)
    try:
        while queue or drv.live:
            while queue and drv.can_admit(queue[0]):
                drv.admit(queue.pop(0))
            if engine.session_active:
                drv.step()
    finally:
        drv.abort()
    return reqs


def prompts(seed, n=3, lens=(5, 17, 40)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, lens[i % len(lens)]).tolist()
            for i in range(n)]


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_reference_logits_match_the_program_prefill(family):
    m, model, params, _ = build(family, 3)
    toks = np.asarray(prompts(3)[2], np.int32)
    logits, _ = jax.jit(lambda p, b: model.prefill(p, b, cache_len=64))(
        params, {"tokens": jnp.asarray(toks[None])})
    ref = check.Reference(REF[family], m, params)
    h = ref.hidden(toks)[len(toks) - 1]
    want = np.asarray(h @ ref.head)
    got = np.asarray(logits[0])
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < 3e-2, err


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_served_tokens_agree_with_the_reference(family):
    m, _, params, engine = build(family, 4)
    reqs = serve(engine, prompts(4, n=5), 12)
    assert all(len(r.tokens) == 12 for r in reqs)
    gaps = check.served_gaps(check.Reference(REF[family], m, params), reqs)
    assert max(gaps) <= SMOKE_GAP[family], gaps


@pytest.mark.parametrize("seed", [5, 6, 7])
@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_control_fails_where_the_program_passes(family, seed):
    m, _, params, engine = build(family, seed)
    reqs = serve(engine, prompts(seed, n=4), 16)
    ref = check.Reference(REF[family], m, params)
    ctl = check.Reference(REF[family], m, params, control=True)
    program = max(check.served_gaps(ref, reqs))
    control = max(check.control_gaps(ref, ctl, reqs))
    assert program <= SMOKE_GAP[family] < control, (program, control)


def test_a_wrong_token_reads_a_wide_gap():
    m, _, params, engine = build("dense", 8)
    reqs = serve(engine, prompts(8, n=2), 8)
    reqs[1].tokens[3] = (reqs[1].tokens[3] + 1) % m["vocab_size"]
    gaps = check.served_gaps(check.Reference("qwen3", m, params), reqs)
    assert gaps[0] <= SMOKE_GAP["dense"] < gaps[1]


def test_sample_takes_live_requests_and_the_longest():
    """Requests still live when the window closed are compared too: the
    longest served request is always in the sample, the rest come from
    the seed, and a request with no token is never drawn."""
    reqs = [Req(rid=i, prompt=[1], max_new=50, arrival=0.0,
                tokens=[0] * (i % 7), finished=1.0 if i % 2 else None)
            for i in range(40)]
    a = check.sample(reqs, 2**31 + 5, 6)
    assert a == check.sample(reqs, 2**31 + 5, 6)
    assert len(a) == 6 and a[0].tokens == [0] * 6 and a[0].finished is None
    assert all(r.tokens for r in a)
    drawn = {r.rid for seed in range(20) for r in check.sample(reqs, seed, 6)}
    assert any(reqs[i].finished is None for i in drawn)
    assert any(reqs[i].finished is not None for i in drawn)
    assert not drawn & {i for i in range(40) if i % 7 == 0}
