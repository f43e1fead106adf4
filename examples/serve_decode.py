"""Serve a small model with batched requests: prefill + KV-cache decode.

  PYTHONPATH=src python examples/serve_decode.py --arch tinyllama-1.1b

Uses the reduced config (CPU container); the full configs serve through
the identical code path on the production mesh (launch/dryrun.py proves
the decode_32k / long_500k lowerings).
"""
import argparse
import time

import jax

from repro.configs import get
from repro.models import api as mapi
from repro.serve.engine import ServeConfig, generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=48)
    args = ap.parse_args()

    cfg = get(args.arch).reduced(dtype="float32", remat=False)
    model = mapi.build(cfg)
    params = model.init(jax.random.PRNGKey(0))

    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab)}
    if cfg.family == "encdec":
        from repro.models.whisper import enc_len_for
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, enc_len_for(cfg, args.prompt_len), cfg.d_model))
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, cfg.vlm_prefix, cfg.d_model))

    t0 = time.time()
    out, steps = generate(model, params, batch,
                          ServeConfig(max_new_tokens=args.new_tokens))
    dt = time.time() - t0
    print(f"arch={args.arch} batch={args.batch} prompt={args.prompt_len} "
          f"new={steps}")
    print(f"decoded {args.batch * steps} tokens in {dt:.2f}s "
          f"({args.batch * steps / dt:,.0f} tok/s)")
    print("sample token ids:", out[0, :16].tolist())


if __name__ == "__main__":
    from repro.core.compat import enable_compile_cache

    enable_compile_cache()
    main()
