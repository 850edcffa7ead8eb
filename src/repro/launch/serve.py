"""Batched serving driver: prefill + greedy decode over a prompt batch.

Prompts are padded to the cache length; prefill returns each example's
true-prompt-end logits (`last_index`) and a cache whose padded slots are
progressively overwritten as decode advances — no recomputation, single
compile for the whole generation loop.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, get_smoke_config
from repro.models import common, lm


def pad_prompts(prompts: list[list[int]], s_max: int, pad_id: int = 0):
    b = len(prompts)
    toks = np.full((b, s_max), pad_id, dtype=np.int32)
    lens = np.zeros(b, dtype=np.int32)
    for i, p in enumerate(prompts):
        p = p[:s_max]
        toks[i, :len(p)] = p
        lens[i] = len(p)
    return jnp.asarray(toks), jnp.asarray(lens)


def generate(params, cfg, prompts: list[list[int]], *, max_new: int,
             ctx: lm.ModelCtx | None = None, enc_inputs=None,
             greedy: bool = True, seed: int = 0):
    """Greedy/sampled generation. Returns [B, max_new] int32 tokens.

    Note: all prompts must share one length for exact ring-buffer (Hymba)
    semantics; mixed lengths are fine for full-cache archs."""
    from repro.launch.mesh import make_mesh

    ctx = ctx or lm.ModelCtx(
        mesh=make_mesh((1, 1), ("data", "model")),
        qc_prefill=64, gla_chunk=64)
    lens_set = {len(p) for p in prompts}
    assert len(lens_set) == 1, \
        "generate() requires uniform prompt lengths (recurrent state + " \
        "ring caches are masked against a single static prompt_len)"
    max_len = max(len(p) for p in prompts)
    s_max = max_len + max_new
    # keep chunked shapes divisible
    s_max = ((s_max + 63) // 64) * 64
    tokens, _lens = pad_prompts(prompts, s_max)
    batch = {"tokens": tokens}
    if enc_inputs is not None:
        batch["enc_inputs"] = enc_inputs

    prefill = jax.jit(lambda p, b: lm.forward_prefill(
        p, b, cfg, ctx, prompt_len=max_len))
    decode = jax.jit(lambda p, c, t, pos: lm.forward_decode(
        p, c, t, pos, cfg, ctx))

    with ctx.mesh:
        logits, cache = prefill(params, batch)
        out = []
        key = jax.random.PRNGKey(seed)
        for i in range(max_new):
            if greedy:
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            else:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits[:, -1]).astype(jnp.int32)
            out.append(nxt)
            pos = jnp.asarray(max_len + i, jnp.int32)
            logits, cache = decode(params, cache, nxt[:, None], pos)
    return np.stack([np.asarray(t) for t in out], axis=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = common.init_params(lm.model_desc(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, min(cfg.vocab, 200),
                                 size=args.prompt_len))
               for _ in range(args.batch)]
    enc = None
    if cfg.encoder_layers:
        enc = jnp.asarray(0.05 * rng.normal(
            size=(args.batch, cfg.encoder_seq, cfg.d_model)), jnp.float32)
    toks = generate(params, cfg, prompts, max_new=args.max_new,
                    enc_inputs=enc)
    print("generated:", toks[:, :8], "... shape", toks.shape)


if __name__ == "__main__":
    main()
