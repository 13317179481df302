"""Faults planted in the timed path, to show that ``correct`` reads false.

A serving cell can go wrong in two ways that a run must catch:

* ``token``: a token altered where it is produced (the engine's sampler
  returns the next id instead of its choice for each request's third
  token);
* ``stale``: the decode step returns its cache or state unchanged, so
  every later token is computed from the state after the prompt.

``planted(name, family)`` swaps the program's function for the broken
one while the block runs; build the engine inside it, since the engine
looks the functions up when it is built.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

NAMES = ("token", "stale")


def _token_altered(good):
    def altered(logits, temps, key, rids, tok_idx):
        tok = good(logits, temps, key, rids, tok_idx)
        return jnp.where(tok_idx == 2, (tok + 1) % logits.shape[-1], tok)
    return altered


def _state_unchanged(good):
    def stale(params, cache, tokens, cfg):
        logits, _ = good(params, cache, tokens, cfg)
        return logits, cache
    return stale


def _target(name: str, family: str):
    from repro.models import transformer, xlstm_lm
    from repro.serving import engine
    if name == "token":
        return engine, "_sample_rows", _token_altered
    if name == "stale":
        if family == "dense":
            return transformer, "decoder_decode_step_paged", _state_unchanged
        if family == "ssm":
            return xlstm_lm, "xlstm_decode_step", _state_unchanged
        raise ValueError(f"no stale-state fault for family {family!r}")
    raise ValueError(f"unknown fault {name!r}; one of {NAMES}")


@contextlib.contextmanager
def planted(name: str, family: str):
    mod, attr, breaker = _target(name, family)
    good = getattr(mod, attr)
    setattr(mod, attr, breaker(good))
    try:
        yield
    finally:
        setattr(mod, attr, good)
