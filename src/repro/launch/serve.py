"""Serving driver: batched prefill + greedy decode for any assigned arch.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
      --batch 4 --prompt-len 64 --gen 32

Runs the reduced config on CPU; the production decode cells
(decode_32k / long_500k on the 512-chip mesh) are exercised by the
dry-run with the same ``build_serve_step``.
"""

from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (non-reduced) config — TPU only")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.launch.shapes import make_batch
    from repro.distributed.sharding import ShardingRules
    from repro.models.registry import get_config
    from repro.models.transformer import LM
    from repro.train.steps import build_prefill_step, build_serve_step

    enable_compile_cache()
    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = cfg.reduced()
    model = LM(cfg)
    mesh = make_host_mesh()
    rules = ShardingRules.default()
    S_total = args.prompt_len + args.gen

    with mesh:
        params = model.init(jax.random.key(0))
        prefill = jax.jit(build_prefill_step(model, mesh, rules))
        serve = jax.jit(build_serve_step(model, mesh, rules),
                        donate_argnums=(2,))

        batch = make_batch(cfg, args.batch, args.prompt_len, kind="prefill")
        t0 = time.time()
        logits, cache = prefill(params, batch)
        # right-pad the cache to the full decode horizon
        def pad_cache(x):
            if x.ndim >= 3 and x.shape[2] == args.prompt_len:
                pad = [(0, 0)] * x.ndim
                pad[2] = (0, args.gen)
                return jnp.pad(x, pad)
            return x
        cache = jax.tree.map(pad_cache, cache)
        tok = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)[:, None]
        t_prefill = time.time() - t0

        toks = [tok]
        t0 = time.time()
        for i in range(args.gen - 1):
            pos = jnp.asarray(args.prompt_len + i, jnp.int32)
            logits, cache, nxt = serve(params, tok, cache, pos)
            tok = nxt[:, None]
            toks.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.time() - t0

    out = np.concatenate([np.asarray(t) for t in toks], axis=1)
    print(f"[serve] {cfg.name}: prefill({args.batch}x{args.prompt_len}) "
          f"{t_prefill*1e3:.1f} ms; decode {args.gen-1} steps "
          f"{t_decode*1e3:.1f} ms "
          f"({(args.gen-1)*args.batch/max(t_decode,1e-9):.1f} tok/s)")
    print("[serve] sample token ids:", out[0, :16].tolist())


if __name__ == "__main__":
    main()
