"""Serving launcher: batched prefill + decode with KV caches.

``python -m repro.launch.serve --arch <id> --smoke --batch 4 --prompt-len 16
--gen 32`` runs prefill over a token batch, then autoregressive decode with
greedy sampling — the serve-side end-to-end driver (deliverable b).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tf
from repro.models import attention as attn_lib
from repro.serve import metrics as serve_metrics
from repro.sharding.policy import make_policy


def prefill_with_caches(params, batch, cfg, max_len: int):
    """Build decode caches by replaying the prompt token-by-token.

    (Production would fuse this; token-replay is exact and reuses the
    decode path, which is what we validate against.)"""
    b, s = batch["tokens"].shape
    caches = tf.init_caches(cfg, b, max_len)
    logits = None
    step = jax.jit(lambda p, c, t, pos: tf.decode_step(p, c, t, pos, cfg))
    for t in range(s):
        logits, caches = step(params, caches, batch["tokens"][:, t:t + 1],
                              jnp.int32(t))
    return logits, caches


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.gen < 1:
        raise SystemExit("--gen must be >= 1: serving emits at least the "
                         "first token (TTFT is undefined otherwise)")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.kind == "encdec":
        raise SystemExit("use examples/whisper_serve.py for enc-dec serving")
    mesh = make_host_mesh(data=1, model=jax.device_count())
    make_policy(cfg, mesh)  # validates the arch has a serving policy
    rng = jax.random.PRNGKey(args.seed)
    params = tf.init_params(rng, cfg)
    max_len = args.prompt_len + args.gen
    tokens = jax.random.randint(rng, (args.batch, args.prompt_len), 0, cfg.vocab_size)

    t0 = time.time()
    logits, caches = prefill_with_caches(params, {"tokens": tokens}, cfg, max_len)
    t_prefill = time.time() - t0

    decode = jax.jit(lambda p, c, t, pos: tf.decode_step(p, c, t, pos, cfg))
    cur = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    generated = [cur]
    t0 = time.time()
    for i in range(args.gen - 1):
        logits, caches = decode(params, caches, cur, jnp.int32(args.prompt_len + i))
        cur = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        generated.append(cur)
    jax.block_until_ready(cur)
    t_decode = time.time() - t0
    out = jnp.concatenate(generated, axis=1)
    # report latency under the shared vocabulary of repro.serve.metrics so
    # this JSON is key-comparable with the simulator's serve_summary()
    n_steps = max(1, args.gen - 1)
    result = {
        "batch": args.batch,
        "prefill_s": round(t_prefill, 3),
        "decode_tok_s": round(args.batch * n_steps / max(t_decode, 1e-9), 1),
        serve_metrics.TTFT_S: round(t_prefill, 6),
        serve_metrics.TPOT_S: round(t_decode / n_steps, 6),
        "generated_shape": list(out.shape),
        "finite": bool(jnp.isfinite(logits).all()),
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    use_compile_cache()
    main()
