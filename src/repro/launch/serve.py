"""Production serving driver: batched prefill + continuous greedy decode.

Runs the real serving path (jitted decode_step against ring-buffer caches)
on whatever devices exist, with simple static batching: requests are padded
to the batch, prefilled in ONE device dispatch (a jitted ``lax.scan`` over
the prompt tokens through decode_step — arch-agnostic: works for attention,
SSM and RWKV caches alike, and bit-identical to the old per-token host
loop), then decoded until max-new-tokens.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --reduced \
      --requests 8 --prompt-len 32 --new-tokens 64
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced
from repro.launch.compile_cache import use_compile_cache
from repro.launch.distributed import make_serve_job
from repro.launch.train import make_mesh_for_devices
from repro.models import Model
from repro.serving import scan_prefill


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="gemma2-2b")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--new-tokens", type=int, default=64)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--temperature", type=float, default=0.0, help="0 = greedy")
    args = p.parse_args(argv)

    use_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if cfg.head != "lm":
        raise SystemExit(f"{cfg.name} is encoder-only: no decode path")
    mesh = make_mesh_for_devices()
    job = make_serve_job(cfg, mesh)
    model = job.model
    print(f"[serve] {cfg.name} on mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"({job.profile.name} profile)")

    params = model.init(jax.random.key(args.seed))
    max_len = args.prompt_len + args.new_tokens
    caches = model.init_cache(args.requests, max_len, dtype=jnp.float32)

    decode = jax.jit(
        lambda p_, c, t, pos: model.decode_step(p_, c, t, pos, dtype=jnp.float32)
    )

    prompts = jax.random.randint(
        jax.random.key(args.seed + 1), (args.requests, args.prompt_len), 0, cfg.vocab_size
    )

    prefill = jax.jit(
        lambda p_, c, toks: scan_prefill(model, p_, c, toks, dtype=jnp.float32)
    )
    t0 = time.time()
    logits, caches = prefill(params, caches, prompts)
    jax.block_until_ready(logits)
    prefill_s = time.time() - t0
    print(f"[serve] prefill: {args.prompt_len} tokens x {args.requests} requests "
          f"in {prefill_s:.2f}s")

    def sample(logits, key):
        if args.temperature <= 0:
            return jnp.argmax(logits[:, -1], axis=-1)
        return jax.random.categorical(key, logits[:, -1] / args.temperature, axis=-1)

    key = jax.random.key(args.seed + 2)
    tok = sample(logits, key)[:, None]
    out = []
    t0 = time.time()
    for i in range(args.new_tokens):
        out.append(np.asarray(tok)[:, 0])
        logits, caches = decode(
            params, caches, tok, jnp.full((args.requests,), args.prompt_len + i, jnp.int32)
        )
        key, sk = jax.random.split(key)
        tok = sample(logits, sk)[:, None]
    jax.block_until_ready(logits)
    decode_s = time.time() - t0
    gen = np.stack(out, axis=1)
    tput = args.requests * args.new_tokens / decode_s
    print(f"[serve] decode: {args.new_tokens} tokens/request, "
          f"{decode_s / args.new_tokens * 1000:.1f} ms/step, {tput:.1f} tok/s aggregate")
    assert np.isfinite(np.asarray(logits)).all(), "non-finite logits"
    for b in range(min(args.requests, 4)):
        print(f"  req {b}: {gen[b][:12].tolist()} ...")
    print("[serve] OK")


if __name__ == "__main__":
    main()
