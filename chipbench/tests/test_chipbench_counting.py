"""Operations and bytes from shapes, against hand counts at smoke size,
so that no roofline or peak share can pass 100% by a counting error."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, counting
from chipbench.reference import qwen3
from chipbench.tests import smoke

DENSE = smoke.MODELS["dense"]
SSM = smoke.MODELS["ssm"]
PEAK = counting.peaks("TPU v5 lite")


def test_dense_weights_by_hand():
    # d 64, 2 layers, 4 heads / 2 kv heads of 16, ffn 128, vocab 256
    attn = 64 * 64 * 2 + 64 * 32 * 2           # wq, wo; wk, wv
    layer = attn + 3 * 64 * 128 + 2 * 16 + 2 * 64
    n = 2 * layer + 64 * 256 + 64               # tied head, final norm
    assert counting.weight_groups(DENSE) == {"matmul": (n, 2 * n),
                                             "cell": (0, 0)}


def _program_leaves(model_dict):
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    from repro.models.layers import PT
    model = build_model(ModelConfig(**model_dict))
    return jax.tree_util.tree_flatten_with_path(
        model.templates, is_leaf=lambda x: isinstance(x, PT))[0]


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_weights_match_the_program_templates(family):
    """Every leaf but the embedding table (a step reads only its rows;
    the tied head counts it once as the head) at its served width."""
    m = smoke.MODELS[family]
    n = nbytes = 0
    for path, t in _program_leaves(m):
        if family == "ssm" and "embed" in jax.tree_util.keystr(path):
            continue
        size = int(np.prod(t.shape))
        n += size
        nbytes += size * jnp.dtype(t.dtype).itemsize
    g = counting.weight_groups(m)
    assert g["matmul"][0] + g["cell"][0] == n
    assert g["matmul"][1] + g["cell"][1] == nbytes


def test_state_bytes_match_the_program_cache():
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    model = build_model(ModelConfig(**SSM))
    shapes = model.cache_shapes(1, 16, jnp.bfloat16)
    want = sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
               for k, s in shapes.items() if k != "pos")
    assert counting.state_bytes(SSM) == want


def test_decode_bytes_by_hand():
    w = counting.weight_groups(DENSE)["matmul"][1]
    per_tok = 2 * 2 * 16 * 2 * 2                # layers, kv heads, hd, K/V, B
    kv = [10, 30]
    want = w + 2 * 64 * 2 + 40 * per_tok + 2 * per_tok
    assert counting.kv_bytes_per_token(DENSE) == per_tok
    assert counting.decode_step_bytes(DENSE, kv) == want
    s = counting.state_bytes(SSM)
    ws = sum(b for _, b in counting.weight_groups(SSM).values())
    assert counting.decode_step_bytes(SSM, [5, 6, 7]) == \
        ws + 3 * 64 * 2 + 3 * 2 * s


def test_paged_attention_bound_by_hand():
    # rows of 17 and 32 positions: 2 + 2 live blocks of 16
    flops = 4 * 4 * 16 * (17 + 32)
    nbytes = 4 * 16 * 2 * 16 * 2 * 2 + 2 * 4 * 16 * 2 * 2
    want = max(flops / PEAK["flops_per_s"], nbytes / PEAK["hbm_bytes_per_s"])
    assert counting.paged_attn_call(DENSE, [17, 32], 16, PEAK) == want


def test_token_flops_by_hand():
    n = counting.weight_groups(DENSE)["matmul"][0]
    assert counting.token_flops(DENSE, 9) == 2 * n + 4 * 2 * 4 * 16 * 9
    assert counting.prefill_flops(DENSE, 3) == sum(
        counting.token_flops(DENSE, c) for c in (1, 2, 3))


def test_prefill_flops_do_not_exceed_what_the_reference_computes():
    """XLA's own count of the plain reference (which also does the
    softmax, norms and the masked half of the scores) bounds ours.  One
    layer: XLA counts a scanned body once, not once per trip."""
    from chipbench.weights import make_weights
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    from repro.models.layers import PT
    one = dict(DENSE, n_layers=1)
    model = build_model(ModelConfig(**one))
    params = make_weights(model.templates, 0, lambda x: isinstance(x, PT))
    s = 64

    def fwd(p, t):
        h = qwen3.hidden(p, t, one, check.mm_f32)
        return h @ qwen3.head(p, one)

    cost = jax.jit(fwd).lower(params, jnp.zeros(s, jnp.int32)).compile() \
        .cost_analysis()
    xla = cost["flops"] if isinstance(cost, dict) else cost[0]["flops"]
    ours = counting.prefill_flops(one, s)
    assert ours <= xla <= 1.6 * ours
