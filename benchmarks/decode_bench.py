"""GPT-2 decode throughput: tokens/sec through the compiled KV-cache loop.

The generation path (`models/generate.py`: chunked prefill + `lax.scan`
decode with per-layer KV caches, top-k/top-p in-loop) is part of the
framework surface beyond the reference contract; this stages its on-chip
number next to the training ladder. Measures GPT-2 125M (the BASELINE
ladder's transformer), batch 8, 128-token prompt, 128 new tokens, bf16.

One JSON line per arm:
    {"metric": "gpt2_decode_tokens_per_sec", ...}   (greedy)
    {"metric": "gpt2_decode_topp_tokens_per_sec", ...}  (top-p 0.9)
    {"metric": "gpt2_prefill_tokens_per_sec", ...}  (prefill phase alone)
    {"metric": "gpt2_decode_only_tokens_per_sec", ...}  (decode phase alone)

The fused metrics above time prompt+generation as one program — the right
number for batch jobs, but it hides that prefill and decode sit on
opposite roofline walls (prefill is a compute-bound matmul over the whole
prompt; decode re-reads every weight per token, bandwidth-bound). The
phase-split arms time them separately: prefill tokens/s doubles as TTFT
(time to first token — prefill samples it), decode-only tokens/s is the
steady per-token rate a serving SLO actually pays (serve_bench.py's p99
decomposes against these two).

Env: GRAFT_BENCH_PLATFORM=cpu -> tiny model CPU self-test;
GRAFT_DECODE_BATCH / GRAFT_DECODE_PROMPT / GRAFT_DECODE_NEW resize.
"""

from __future__ import annotations

import json
import os
import time

import _bootstrap  # noqa: F401  (repo root on sys.path)
from _roofline import guard

CPU_SELF_TEST = os.environ.get("GRAFT_BENCH_PLATFORM") == "cpu"
BATCH = max(1, int(os.environ.get("GRAFT_DECODE_BATCH", "2" if CPU_SELF_TEST else "8")))
PROMPT = max(2, int(os.environ.get("GRAFT_DECODE_PROMPT", "16" if CPU_SELF_TEST else "128")))
NEW = max(2, int(os.environ.get("GRAFT_DECODE_NEW", "16" if CPU_SELF_TEST else "128")))
REPS = max(1, int(os.environ.get("GRAFT_DECODE_REPS", "1" if CPU_SELF_TEST else "5")))


def main() -> None:
    if CPU_SELF_TEST:
        import jax

        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.runtime.cache import (
        enable_compile_cache,
    )

    enable_compile_cache()

    from pytorch_distributedtraining_tpu.models.gpt2 import GPT2, GPT2Config
    from pytorch_distributedtraining_tpu.models.generate import generate

    if CPU_SELF_TEST:
        cfg = GPT2Config(
            vocab_size=256, n_positions=64, n_embd=32, n_layer=2, n_head=2,
            dtype=jnp.bfloat16,
        )
    else:  # GPT-2 125M (BASELINE ladder config 4's model), bf16 compute
        cfg = GPT2Config(dtype=jnp.bfloat16)
    model = GPT2(cfg, decode=True)
    train_model = GPT2(cfg, decode=False)
    rng = np.random.default_rng(0)
    # one prompt per rep PLUS a warmup-only prompt: every timed call
    # decodes inputs no earlier call has seen, so nothing between the host
    # and the device can answer it from a cache
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (REPS + 1, BATCH, PROMPT)),
        jnp.int32,
    )
    prompt = prompts[REPS]  # warmup-only
    params = train_model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, PROMPT), jnp.int32)
    )["params"]

    # Roofline: each decode step re-reads
    # every weight once, so tokens/sec <= BATCH * HBM_BW / weight_bytes.
    # 2 TB/s is a deliberately generous ceiling (v5e-class HBM is ~819
    # GB/s); a number above even THIS bound is an instrument failure
    # (async dispatch not actually synced), never a measurement. The r4
    # artifact (2.55M tok/s greedy at batch 8) violated it ~100x.
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    weight_bytes = 2.0 * n_params  # bf16 compute path
    roofline_tok_s = BATCH * 2e12 / weight_bytes

    for metric, kwargs in (
        ("gpt2_decode_tokens_per_sec", dict(temperature=0.0)),
        ("gpt2_decode_topp_tokens_per_sec", dict(top_p=0.9)),
    ):
        run = jax.jit(
            lambda p, pr: generate(
                model, p, pr, NEW, rng=jax.random.PRNGKey(1), **kwargs
            )
        )
        out = run(params, prompt)  # compile + warm
        jax.block_until_ready(out)
        # pre-warm the tiny chaining ops too (they jit-compile on first
        # use; on CPU self-test their compile dwarfed a whole greedy rep)
        warm_carry = out[:, -1].max().astype(jnp.int32)
        jax.block_until_ready((prompt + warm_carry) % cfg.vocab_size)
        # Chain the reps device-side: rep i's prompt depends on rep i-1's
        # output, so queue-level overlap cannot collapse the sequence; the
        # final int() is a host fetch that transitively waits on EVERY rep.
        carry = jnp.int32(0)
        t0 = time.perf_counter()
        for i in range(REPS):
            pr = (prompts[i] + carry) % cfg.vocab_size
            out = run(params, pr)
            carry = out[:, -1].max().astype(jnp.int32)
        fetched = int(carry)  # host round-trip ends the timed region
        dt = (time.perf_counter() - t0) / REPS
        assert out.shape == (BATCH, PROMPT + NEW), out.shape
        assert 0 <= fetched < cfg.vocab_size, fetched
        tok_s = BATCH * NEW / dt
        guard(
            metric, tok_s, "tokens/sec", roofline_tok_s,
            f"batch {BATCH} x 2 TB/s HBM / {weight_bytes / 1e6:.0f} MB "
            f"weights read per step",
        )
        print(json.dumps({
            "metric": metric,
            "value": round(tok_s, 1),
            "unit": "tokens/sec",
            "ms_per_token": round(dt / NEW * 1e3, 3),
            "roofline_tok_s": round(roofline_tok_s, 1),
        }), flush=True)

    # -- phase split: prefill alone (TTFT) and decode alone ----------------
    from pytorch_distributedtraining_tpu.models.generate import (
        init_cache, sample_logits,
    )

    @jax.jit
    def prefill(params, prompt):
        cache = init_cache(model, BATCH, PROMPT + NEW)
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, prompt, mutable=["cache"]
        )
        tok = sample_logits(
            logits[:, -1], jax.random.PRNGKey(1), temperature=0.0
        )
        return mutated["cache"], tok

    @jax.jit
    def decode_only(params, cache, tok):
        def step(carry, step_rng):
            cache, tok = carry
            logits, mutated = model.apply(
                {"params": params, "cache": cache}, tok[:, None],
                mutable=["cache"],
            )
            nxt = sample_logits(logits[:, -1], step_rng, temperature=0.0)
            return (mutated["cache"], nxt), tok

        keys = jax.random.split(jax.random.PRNGKey(2), NEW - 1)
        (_, last), _ = jax.lax.scan(step, (cache, tok), keys)
        return last

    cache, tok = prefill(params, prompt)  # compile + warm both phases
    jax.block_until_ready(decode_only(params, cache, tok))

    # prefill: chain rep i's prompt on rep i-1's sampled token (same
    # anti-memoization discipline as the fused arms)
    carry = jnp.int32(0)
    t0 = time.perf_counter()
    for i in range(REPS):
        cache, tok = prefill(params, (prompts[i] + carry) % cfg.vocab_size)
        carry = tok.max().astype(jnp.int32)
    int(carry)
    dt_prefill = (time.perf_counter() - t0) / REPS
    # prefill is compute-bound: ~2 * n_params flops per prompt token
    prefill_roof = 4e14 / (2.0 * n_params)
    prefill_tok_s = BATCH * PROMPT / dt_prefill
    guard(
        "gpt2_prefill_tokens_per_sec", prefill_tok_s, "tokens/sec",
        prefill_roof,
        f"400 TFLOP/s peak / {2 * n_params / 1e6:.0f} MFLOP per token",
    )
    print(json.dumps({
        "metric": "gpt2_prefill_tokens_per_sec",
        "value": round(prefill_tok_s, 1),
        "unit": "tokens/sec",
        "ttft_ms": round(dt_prefill * 1e3, 3),
        "prompt_tokens": BATCH * PROMPT,
    }), flush=True)

    # decode-only: NEW-1 scan steps (the prefill already sampled token #1);
    # chain on the previous rep's last token
    t0 = time.perf_counter()
    for _ in range(REPS):
        tok = decode_only(params, cache, tok)
    int(tok.max())
    dt_decode = (time.perf_counter() - t0) / REPS
    decode_tok_s = BATCH * (NEW - 1) / dt_decode
    guard(
        "gpt2_decode_only_tokens_per_sec", decode_tok_s, "tokens/sec",
        roofline_tok_s,
        f"batch {BATCH} x 2 TB/s HBM / {weight_bytes / 1e6:.0f} MB "
        f"weights read per step",
    )
    print(json.dumps({
        "metric": "gpt2_decode_only_tokens_per_sec",
        "value": round(decode_tok_s, 1),
        "unit": "tokens/sec",
        "ms_per_token": round(dt_decode / (NEW - 1) * 1e3, 3),
        "ttft_ms": round(dt_prefill * 1e3, 3),
        "roofline_tok_s": round(roofline_tok_s, 1),
    }), flush=True)


if __name__ == "__main__":
    main()
