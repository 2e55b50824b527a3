"""Serving launcher: prefill a batch of prompts, then greedy-decode.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b --smoke \
        --batch 4 --prompt-len 32 --gen 16

:func:`serve` is the whole path (random weights from ``seed``, params, cache
and inputs placed with the shardings ``make_prefill`` / ``make_serve_step``
return); ``main`` and ``chip_smoke.py`` both call it.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Callable


@dataclasses.dataclass
class ServeRun:
    """What one :func:`serve` call produced, for callers that check it."""

    prompt: Any  # (batch, prompt_len) int32 tokens
    prefill_logits: Any  # (batch, vocab) f32 at the last prompt position
    generated: Any  # (batch, gen + 1) greedy tokens, the first from prefill
    decode_logits: Any  # (gen, batch, vocab) f32
    # decode_step(cache, tokens (batch, 1), position (batch,)) ->
    # (next_tokens, logits, cache): the compiled step with this run's params,
    # inputs placed with the step's shardings; the cache is donated
    decode_step: Callable
    new_cache: Callable[[], Any]  # a zeroed, placed (batch, max_seq) cache
    compile_s: float  # prefill + decode-step compilation
    prefill_s: float
    decode_s: float


def serve(cfg, mesh, *, batch: int, prompt_len: int, gen: int, seed: int = 0) -> ServeRun:
    """Prefill ``batch`` random prompts of ``prompt_len`` tokens on ``mesh``,
    then greedy-decode ``gen`` tokens; weights and prompts come from
    ``seed``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models.lm import init_lm
    from repro.serve.decode import init_cache, make_prefill, make_serve_step

    max_seq = prompt_len + gen
    dtype = jnp.dtype(cfg.dtype)
    with mesh:
        prefill_fn, _, pre_out_sh, _ = make_prefill(cfg, mesh, batch, prompt_len)
        serve_fn, in_sh, out_sh, _ = make_serve_step(cfg, mesh, batch, max_seq)
        params_sh, cache_sh, tok_sh, pos_sh = in_sh

        k_params, k_prompt = jax.random.split(jax.random.PRNGKey(seed))
        params = jax.jit(lambda k: init_lm(k, cfg, dtype), out_shardings=params_sh)(k_params)
        prompt = jax.device_put(
            jax.random.randint(k_prompt, (batch, prompt_len), 0, cfg.vocab_size), tok_sh
        )
        inputs = (
            {"embeddings": jax.device_put(
                jax.random.normal(k_prompt, (batch, prompt_len, cfg.d_model), dtype),
                NamedSharding(mesh, P(*tok_sh.spec, None)),
            )}
            if cfg.frontend
            else {"tokens": prompt}
        )
        new_cache = jax.jit(
            lambda: init_cache(cfg, batch, max_seq), out_shardings=cache_sh
        )

        t0 = time.perf_counter()
        # inputs are placed, so prefill's input shardings follow them
        prefill = (
            jax.jit(prefill_fn, out_shardings=pre_out_sh).lower(params, inputs).compile()
        )
        step = jax.jit(
            serve_fn, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=(1,)
        )
        cache = new_cache()
        position = jax.device_put(jnp.full((batch,), prompt_len, jnp.int32), pos_sh)
        tok0 = jax.device_put(jnp.zeros((batch, 1), jnp.int32), tok_sh)
        step = step.lower(params, cache, tok0, position).compile()
        compile_s = time.perf_counter() - t0

        def decode_step(cache, tokens, position):
            return step(
                params, cache, jax.device_put(tokens, tok_sh),
                jax.device_put(position, pos_sh),
            )

        t0 = time.perf_counter()
        logits, _prefill_cache = jax.block_until_ready(prefill(params, inputs))
        prefill_s = time.perf_counter() - t0
        next_tok = jnp.argmax(logits, -1).astype(jnp.int32)

        # Decode from a zeroed max_seq cache: prefill's cache is not inserted
        # into it yet, so decoded tokens attend only to themselves.
        out, step_logits = [next_tok], []
        t0 = time.perf_counter()
        for i in range(gen):
            next_tok, lg, cache = decode_step(cache, next_tok[:, None], position + i)
            out.append(next_tok)
            step_logits.append(lg)
        jax.block_until_ready(next_tok)
        decode_s = time.perf_counter() - t0

    return ServeRun(
        prompt=prompt,
        prefill_logits=logits,
        generated=jnp.stack(out, axis=1),
        decode_logits=jnp.stack(step_logits),
        decode_step=decode_step,
        new_cache=new_cache,
        compile_s=compile_s,
        prefill_s=prefill_s,
        decode_s=decode_s,
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.configs.registry import get_config, get_smoke_config
    from repro.launch.mesh import make_launcher_mesh

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    run = serve(
        cfg, make_launcher_mesh(), batch=args.batch, prompt_len=args.prompt_len,
        gen=args.gen,
    )
    print(f"[serve] compile {run.compile_s:.2f}s")
    print(f"[serve] prefill({args.batch}x{args.prompt_len}) {run.prefill_s:.2f}s")
    print(
        f"[serve] decoded {args.gen} tokens x {args.batch} seqs in {run.decode_s:.2f}s "
        f"({args.gen * args.batch / run.decode_s:.1f} tok/s)"
    )
    print("[serve] sample continuation:", run.generated[0, :10].tolist())


if __name__ == "__main__":
    main()
