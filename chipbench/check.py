"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample
of the requests the engine served tokens to, finished or still live when
the window closed (drawn from the seed, the request with the most served
tokens always in it), is run through the plain
float32 reference of the configuration's family, one sequence at a time:
the prompt followed by the served tokens, teacher-forced.  At every
position that produced a served token, the gap is the reference's best
logit minus the reference's logit of the served token; the number
compared is the widest gap over the sample.  Greedy tokens that agree
with the reference read 0; a token the program chose on a near-tie of
its own rounding reads the small difference of that tie; a wrong token
reads the spread of the logits.

The control puts the reference in the program's place at the precision
below the one the configuration serves in (bfloat16 weights and
activations): every matrix product's operands are rounded to float8
e4m3 with a per-tensor scale.  At each of the same positions it reads
the gap of the token the control puts first.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

#: sequences are right-padded to a multiple of this before the reference
#: runs (causal, so padding changes no earlier position): a handful of
#: compiled shapes serve every length
BUCKET = 512
#: rows of the output projection computed at once
HEAD_ROWS = 512
F8_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)


def mm_f32(a, b, spec):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _to_f8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm_f8(a, b, spec):
    return mm_f32(_to_f8(a), _to_f8(b), spec)


def sample(reqs, seed: int, n: int) -> list:
    """Up to ``n`` requests that were served tokens, finished or still
    live: the one with the most served tokens, then others drawn from
    the seed."""
    done = sorted((r for r in reqs if r.tokens), key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


class Reference:
    """Jitted hidden-state and head passes of one family's reference."""

    def __init__(self, family: str, model: dict, params, *, control=False):
        mod = importlib.import_module(f"chipbench.reference.{family}")
        mm = mm_f8 if control else mm_f32
        self.params = params
        self.head = mod.head(params, model)
        if control:
            self.head = _to_f8(self.head)
        last = _to_f8 if control else (lambda h: h)
        self._hidden = jax.jit(lambda p, t: jnp.pad(
            last(mod.hidden(p, t, model, mm)), ((0, HEAD_ROWS), (0, 0))))

    def hidden(self, tokens: np.ndarray):
        """Hidden states of ``tokens``, zero rows after them: the array
        holds at least ``HEAD_ROWS`` rows past the last token."""
        n = len(tokens)
        padded = np.zeros(-(-n // BUCKET) * BUCKET, np.int32)
        padded[:n] = tokens
        with jax.default_matmul_precision("highest"):
            return self._hidden(self.params, jnp.asarray(padded))


@jax.jit
def _row_stats(h, w, start, served):
    hb = jax.lax.dynamic_slice_in_dim(h, start, HEAD_ROWS)
    logits = mm_f32(hb, w, "sd,dv->sv")
    best = jnp.max(logits, axis=-1)
    pick = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return best, pick, jnp.argmax(logits, axis=-1)


def _head_pass(h, w, first, served):
    """(best logit, logit of ``served[j]``, argmax) for rows
    ``first + j`` of h."""
    outs = []
    n = len(served)
    for i in range(0, n, HEAD_ROWS):
        rows = min(HEAD_ROWS, n - i)
        sb = np.zeros(HEAD_ROWS, np.int32)
        sb[:rows] = served[i:i + rows]
        best, pick, arg = _row_stats(h, w, np.int32(first + i),
                                     jnp.asarray(sb))
        outs.append((np.asarray(best)[:rows], np.asarray(pick)[:rows],
                     np.asarray(arg)[:rows]))
    return tuple(np.concatenate(x) for x in zip(*outs))


def _positions(r):
    """Teacher-forced input and the served token at each scored row."""
    seq = np.asarray(list(r.prompt) + list(r.tokens[:-1]), np.int32)
    return seq, np.asarray(r.tokens, np.int32), len(r.prompt) - 1


def position_gaps(ref: Reference, reqs) -> list[np.ndarray]:
    """Per request, per served token: its gap below the reference's best
    logit at that position."""
    out = []
    for r in reqs:
        seq, served, first = _positions(r)
        best, pick, _ = _head_pass(ref.hidden(seq), ref.head, first,
                                   served)
        out.append(best - pick)
    return out


def served_gaps(ref: Reference, reqs) -> list[float]:
    """Per request: the widest gap of a served token below the
    reference's best logit."""
    return [float(np.max(g)) for g in position_gaps(ref, reqs)]


def control_position_gaps(ref: Reference, ctl: Reference,
                          reqs) -> list[np.ndarray]:
    """Per request, per position: the gap, below the reference's best
    logit, of the token the control puts first."""
    out = []
    for r in reqs:
        seq, served, first = _positions(r)
        _, _, choice = _head_pass(ctl.hidden(seq), ctl.head, first,
                                  served)
        best, pick, _ = _head_pass(ref.hidden(seq), ref.head, first,
                                   choice)
        out.append(best - pick)
    return out


def control_gaps(ref: Reference, ctl: Reference, reqs) -> list[float]:
    """Per request: the widest gap of the control's first choices."""
    return [float(np.max(g)) for g in control_position_gaps(ref, ctl, reqs)]


def judge(gaps: list[float], n_tokens: int, limits: dict) -> tuple:
    """(correct, checks): each number compared beside its limit."""
    widest = max(gaps) if gaps else None
    gap_ok = widest is not None and widest <= limits["logit_gap"]
    tok_ok = n_tokens >= limits["compared_tokens"]
    checks = {
        "logit_gap": {"value": widest, "limit": limits["logit_gap"],
                      "rule": "at most", "ok": gap_ok},
        "compared_tokens": {"value": n_tokens,
                            "limit": limits["compared_tokens"],
                            "rule": "at least", "ok": tok_ok},
    }
    return gap_ok and tok_ok, checks
