"""Serving launcher: prefill a batch of prompts, then decode greedily.

Without ``--smoke`` the registered config serves from a ``("data",
"model")`` mesh over every device present; ``--smoke`` serves the reduced
config from the default device (how it runs on a CPU).

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-370m \
      --prompt-len 512 --decode-steps 16 --batch 4
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve \
      --arch mamba2-370m --smoke --prompt-len 64 --decode-steps 16 --batch 2
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.train.steps import make_serve_bundle


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode-steps", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg, mesh = smoke_config(cfg), None
    else:
        mesh = make_host_mesh()
    max_len = args.prompt_len + args.decode_steps
    bundle = make_serve_bundle(cfg, mesh, batch=args.batch, max_len=max_len)
    key = jax.random.PRNGKey(args.seed)
    params = bundle.model.init(key)
    if bundle.param_shardings is not None:
        params = jax.device_put(params, bundle.param_shardings)
    tokens = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32
    )
    fe = None
    if cfg.frontend is not None:
        fe = jnp.zeros((args.batch, cfg.frontend_positions, cfg.d_model), jnp.bfloat16)

    t0 = time.perf_counter()
    if cfg.enc_dec:
        logits, cache = bundle.prefill_fn(params, tokens, fe)
    elif cfg.frontend is not None:
        logits, cache = bundle.prefill_fn(params, tokens, fe)
    else:
        logits, cache = bundle.prefill_fn(params, tokens)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    print(f"prefill {args.prompt_len} tokens x{args.batch}: {t_prefill*1e3:.1f} ms")

    out_tokens = []
    nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t0 = time.perf_counter()
    for i in range(args.decode_steps):
        out_tokens.append(np.asarray(nxt)[:, 0])
        logits, cache = bundle.decode_fn(
            params, cache, nxt, jnp.asarray(args.prompt_len + i, jnp.int32)
        )
        nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    jax.block_until_ready(logits)
    dt = (time.perf_counter() - t0) / args.decode_steps
    print(f"decode: {dt*1e3:.2f} ms/token")
    print("generated:", np.stack(out_tokens, 1)[:, :12])


if __name__ == "__main__":
    main()
