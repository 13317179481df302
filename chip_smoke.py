"""Bring-up check: the serving path on one TPU at published widths.

  python chip_smoke.py

One process, no arguments, no child processes.  In order:

* device: fails unless JAX's first device is a TPU, then prints its
  platform, ``device_kind`` and the device count, and the peak row that
  ``repro.serving.attribution`` keeps for that kind;
* kernels: fails unless the kernel implementation is ``pallas``
  (``REPRO_KERNEL_IMPL`` unset or ``pallas``); the paged decode step of
  the phase-(a) engine must hold the Pallas kernel (``pallas_call`` in its
  jaxpr, ``tpu_custom_call`` in its compiled text);
* serve, in-process through ``repro.launch.serve.main``, greedy, prompts
  of 37, 200 and 700 tokens, ``--cache-len 1024 --max-new 16``:
  (a) qwen3-0.6b on the paged KV layout, (b) qwen3-0.6b on the dense
  layout (the flash prefill kernel), (c) qwen3-0.6b on two cluster
  replicas, (d) xlstm-350m on its per-slot recurrent state.  Every
  request must return ``max_new`` tokens inside the vocabulary, and a
  second ``generate`` on the warm engine must return the same tokens.
  Each phase prints its first-call time (model build, compile, serve)
  apart from the steady time of the warm rerun, and the device's
  ``peak_bytes_in_use`` so far;
* logits: qwen3-0.6b prefill-plus-first-decode logits of one 200-token
  prompt on the Pallas paged path against the same computation traced
  afresh on the XLA path, both on the chip, within ``LOGITS_RTOL``.

Any failed check raises, so the exit code is non-zero and the result
line is not printed.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
These are bring-up timings, not a benchmark.

``run_phases`` is the whole check minus the device and implementation
gates; the tier-1 tests run it at smoke widths on the CPU with the
kernels in interpret mode (``tests/test_chip_smoke.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config, smoke_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving import MachineSpec, Request  # noqa: E402
from repro.serving.kvcache import blocks_needed  # noqa: E402

#: Pallas-vs-XLA logits bound: max |pallas - xla| over max |xla|.  Both
#: paths read the same bf16 weights and KV and accumulate in f32; they
#: differ in summation order and in where attention outputs round to
#: bf16 (unit roundoff 2^-8 = 3.9e-3) across 28 layers.  A wrong mask or
#: block gather moves the logits by O(1) of their scale.
LOGITS_RTOL = 5e-2


MAX_BATCH, BLOCK_SIZE, SEED = 4, 16, 0


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the CPU twin of this check shrinks."""
    prompt_lens: tuple = (37, 200, 700)
    cache_len: int = 1024
    max_new: int = 16


class SmokeFailure(RuntimeError):
    """A check of the bring-up run failed."""


def _require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def check_device():
    """The first JAX device, which must be a TPU."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX's first device is platform "
                 f"{d.platform!r} ({d.device_kind!r}); this check runs only "
                 "on the chip")
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    return d


def check_kernel_impl():
    env = os.environ.get("REPRO_KERNEL_IMPL")
    if env and env != "pallas":
        sys.exit(f"chip_smoke: REPRO_KERNEL_IMPL={env!r} takes the Pallas "
                 "kernels off the chip path; unset it")
    impl = ops.default_impl()
    if impl != "pallas":
        sys.exit(f"chip_smoke: kernel implementation is {impl!r}, not "
                 "'pallas'")
    print(f"[kernels] impl={impl}")


def _cfg(arch, smoke):
    return smoke_config(arch) if smoke else get_config(arch)


def _prompts(sizes, vocab):
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, vocab, n).tolist() for n in sizes.prompt_lens]


def _peak_bytes():
    """The device's peak bytes in use so far (None where the backend
    keeps no memory stats, as on the CPU)."""
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def _compile_checked(fn, args, expect_custom_call):
    """Compile jitted ``fn`` at ``args``; returns (executable, jaxpr holds
    a pallas_call, compiled text holds tpu_custom_call).  Fails when
    ``expect_custom_call`` (unless None) disagrees with the text."""
    in_jaxpr = "pallas_call" in str(jax.make_jaxpr(fn)(*args))
    compiled = fn.lower(*args).compile()
    in_hlo = "tpu_custom_call" in compiled.as_text()
    if expect_custom_call is not None:
        _require(in_hlo == expect_custom_call,
                 f"tpu_custom_call in compiled text: {in_hlo}, expected "
                 f"{expect_custom_call}")
    return compiled, in_jaxpr, in_hlo


def serve_phase(name, arch, flags, sizes, *, smoke):
    """Serve the prompts through ``repro.launch.serve.main`` and check
    the tokens; returns the engine."""
    cfg = _cfg(arch, smoke)
    prompts = _prompts(sizes, cfg.vocab_size)
    argv = ["--arch", arch, "--max-new", str(sizes.max_new),
            "--cache-len", str(sizes.cache_len),
            "--max-batch", str(MAX_BATCH),
            "--block-size", str(BLOCK_SIZE),
            "--seed", str(SEED),
            "--prompts", *(" ".join(map(str, p)) for p in prompts),
            *flags, *(["--smoke"] if smoke else [])]
    print(f"[phase {name}] {arch} flags={flags} prompt_lens="
          f"{list(sizes.prompt_lens)} cache_len={sizes.cache_len} "
          f"max_new={sizes.max_new}", flush=True)
    t0 = time.perf_counter()
    eng, tokens = serve.main(argv)
    first_s = time.perf_counter() - t0
    _require(sorted(tokens) == list(range(len(prompts))),
             f"phase {name}: answered rids {sorted(tokens)}")
    for rid, toks in tokens.items():
        _require(len(toks) == sizes.max_new,
                 f"phase {name}: rid {rid} returned {len(toks)} tokens, "
                 f"expected {sizes.max_new}")
        _require(all(0 <= t < cfg.vocab_size for t in toks),
                 f"phase {name}: rid {rid} token outside [0, "
                 f"{cfg.vocab_size}): {toks}")
    reqs = [Request(p, sizes.max_new, 0.0, rid=i)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    again = {r.rid: r.tokens for r in eng.generate(reqs)}
    steady_s = time.perf_counter() - t0
    _require(again == tokens,
             f"phase {name}: the warm rerun changed greedy tokens")
    print(f"[phase {name}] ok requests={len(tokens)} "
          f"tokens_per_request={sizes.max_new} "
          f"first_call_s={first_s:.3f} steady_s={steady_s:.3f} "
          f"peak_bytes_in_use={_peak_bytes()}", flush=True)
    return eng


def decode_kernel_check(eng, *, expect_custom_call):
    """The engine's own jitted paged decode step holds the paged kernel."""
    cache = jax.eval_shape(lambda: eng.model.paged_cache_init(
        batch=eng.max_batch, n_blocks=eng.allocator.n_blocks,
        block_size=eng.block_size, max_blocks=eng.max_blocks,
        dtype=eng.model.cache_dtype(eng.params)))
    toks = jax.ShapeDtypeStruct((eng.max_batch, 1), jnp.int32)
    _, in_jaxpr, in_hlo = _compile_checked(
        eng._decode, (eng.params, cache, toks), expect_custom_call)
    _require(in_jaxpr, "the paged decode step traced no pallas_call")
    print(f"[kernels] paged decode step: pallas_call=yes "
          f"tpu_custom_call={'yes' if in_hlo else 'no'}", flush=True)


def _paged_logits(model, params, prompt, sizes, impl, token,
                  expect_custom_call):
    """Prefill ``prompt`` chunk by chunk into a one-slot paged cache, then
    decode one step on ``token`` (None: the prefill's greedy token), all
    traced under ``impl``.  Returns (prefill logits, decode logits,
    decoded-on token)."""
    bs = BLOCK_SIZE
    n = len(prompt)
    n_used = blocks_needed(n + 1, bs)
    with ops.impl_scope(impl):
        cache = model.paged_cache_init(
            batch=1, n_blocks=n_used + 1, block_size=bs,
            max_blocks=blocks_needed(sizes.cache_len, bs),
            dtype=model.cache_dtype(params))
        cache["bt"] = cache["bt"].at[0, :n_used].set(
            jnp.arange(1, n_used + 1, dtype=jnp.int32))
        # fresh functions: a jitted function keeps the implementation it
        # was first traced under
        prefill = jax.jit(lambda p, c, b, ch, ln:
                          model.prefill_paged(p, c, b, 0, ch, ln))
        decode = jax.jit(lambda p, c, t: model.decode_paged(p, c, t))
        padded = np.zeros(blocks_needed(n, bs) * bs, np.int32)
        padded[:n] = prompt
        chunk0 = {"tokens": jnp.asarray(padded[None, :bs])}
        pre_args = (params, cache, chunk0, jnp.int32(0), jnp.int32(n))
        dec_args = (params, cache, jnp.zeros((1, 1), jnp.int32))
        is_pallas = impl != "xla"
        compiled = []
        for fn, args, what in ((prefill, pre_args, "prefill chunk"),
                               (decode, dec_args, "decode step")):
            exe, in_jaxpr, _ = _compile_checked(
                fn, args, expect_custom_call if is_pallas else False)
            _require(in_jaxpr == is_pallas,
                     f"{impl} {what}: pallas_call in jaxpr is {in_jaxpr}")
            compiled.append(exe)
        prefill, decode = compiled     # run exactly what was inspected
        for c in range(blocks_needed(n, bs)):
            batch = {"tokens": jnp.asarray(padded[None, c * bs:(c + 1) * bs])}
            logits, cache = prefill(params, cache, batch, jnp.int32(c),
                                    jnp.int32(n))
        if token is None:
            token = int(jnp.argmax(logits[0]))
        dlogits, _ = decode(params, cache, jnp.full((1, 1), token, jnp.int32))
    return np.asarray(logits), np.asarray(dlogits), token


def logits_phase(sizes, *, smoke, impl, expect_custom_call):
    """Pallas paged path vs XLA paged path on one prompt."""
    cfg = _cfg("qwen3-0.6b", smoke)
    model = build_model(cfg)
    params = model.init(jax.random.key(SEED))
    prompt = _prompts(sizes, cfg.vocab_size)[1]
    ref_pre, ref_dec, tok = _paged_logits(model, params, prompt, sizes,
                                          "xla", None, expect_custom_call)
    got_pre, got_dec, _ = _paged_logits(model, params, prompt, sizes, impl,
                                        tok, expect_custom_call)
    for what, got, ref in (("prefill", got_pre, ref_pre),
                           ("decode", got_dec, ref_dec)):
        _require(got.shape == ref.shape == (1, cfg.vocab_size)
                 and np.isfinite(got).all() and np.isfinite(ref).all(),
                 f"{what} logits: shapes {got.shape} {ref.shape} or "
                 "non-finite values")
        err = float(np.abs(got - ref).max() / np.abs(ref).max())
        print(f"[logits] {cfg.name} prompt_len={len(prompt)} {what}: "
              f"{impl} vs xla max_abs_err/max_abs={err:.3e} "
              f"tolerance={LOGITS_RTOL:.0e}", flush=True)
        _require(err <= LOGITS_RTOL,
                 f"{what} logits: {impl} vs xla error {err:.3e} above "
                 f"{LOGITS_RTOL:.0e}")


def run_phases(sizes=Sizes(), *, smoke=False, impl="pallas"):
    """Every phase after the device and implementation gates, under
    kernel implementation ``impl``.  The compiled Pallas steps must hold
    ``tpu_custom_call`` when ``impl`` is "pallas"; in interpret mode
    nothing compiles for a TPU, and only the jaxpr is checked."""
    expect_custom_call = True if impl == "pallas" else None
    # the cluster replicas' device pools each hold the whole shared pool:
    # size it to the cluster's slots so both copies fit
    n_blocks = MAX_BATCH * blocks_needed(sizes.cache_len, BLOCK_SIZE) + 1
    phases = (("a", "qwen3-0.6b", ["--kv-layout", "paged"]),
              ("b", "qwen3-0.6b", ["--kv-layout", "dense"]),
              ("c", "qwen3-0.6b", ["--replicas", "2",
                                   "--n-blocks", str(n_blocks)]),
              ("d", "xlstm-350m", []))
    with ops.impl_scope(impl):
        for name, arch, flags in phases:
            eng = serve_phase(name, arch, flags, sizes, smoke=smoke)
            if name == "a":
                decode_kernel_check(eng,
                                    expect_custom_call=expect_custom_call)
            del eng                   # free its pool before the next phase
            gc.collect()
    logits_phase(sizes, smoke=smoke, impl=impl,
                 expect_custom_call=expect_custom_call)


def main():
    device = check_device()
    check_kernel_impl()
    spec = MachineSpec.for_kind(device.device_kind)
    print(f"[peaks] {device.device_kind}: {spec.name} "
          f"peak_flops={spec.peak_flops:.3e} mem_bw={spec.mem_bw:.3e}")
    cache_dir = enable_compile_cache()
    n_entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"[cache] dir={cache_dir} entries_at_start={n_entries}",
          flush=True)
    t0 = time.perf_counter()
    run_phases()
    print(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True,
                      "device": {"platform": device.platform,
                                 "kind": device.device_kind,
                                 "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
