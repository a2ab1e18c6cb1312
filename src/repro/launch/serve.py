"""Batched serving driver: pipelined decode with stage-local KV caches.

    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b \
        --devices 8 --stages 4 --batch 8 --tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch.mesh import make_mesh
from repro.models.build import build
from repro.pipeline.decode import DecodeOptions, make_serve_fn
from repro.pipeline.sharding import partition_for


def build_server(arch: str, *, data: int, stages: int, layers: int | None,
                 batch: int, cache_len: int, reduced: bool = True):
    cfg = registry.model_config(arch, layers, full_size=not reduced)
    model = build(cfg, num_stages=stages)
    mesh = make_mesh(data, stages)
    key = jax.random.key(0)
    sp = model.init_stage_params(key)
    io = model.init_io_params(jax.random.fold_in(key, 1))
    partition = partition_for(model, sp, io)
    rows_per_shard = batch // data
    opts = DecodeOptions(mb_rows=1, cache_len=cache_len)
    wrap, _, _ = make_serve_fn(model, mesh, opts, num_groups=rows_per_shard)
    serve_step = jax.jit(wrap(partition))
    one = model.init_layer_cache(batch, cache_len,
                                 enc_len=max(1, cache_len // 4))
    caches = jax.tree.map(
        lambda x: jnp.broadcast_to(
            x[None, None], (stages, model.l_max) + x.shape).copy(), one)
    return dict(cfg=cfg, model=model, mesh=mesh, serve_step=serve_step,
                sp=sp, io=io, caches=caches)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    args = ap.parse_args()
    data = args.devices // args.stages
    s = build_server(args.arch, data=data, stages=args.stages,
                     layers=args.layers, batch=args.batch,
                     cache_len=args.cache_len)
    cfg = s["cfg"]
    tokens = jax.random.randint(jax.random.key(7), (args.batch,), 0,
                                cfg.vocab_size).astype(jnp.int32)
    caches = s["caches"]
    seqs = [np.asarray(tokens)]
    t0 = time.time()
    for pos in range(args.tokens):
        batch = {"tokens": tokens}
        if cfg.embed_input:
            batch = {"embeds": jax.random.normal(
                jax.random.key(pos), (args.batch, 1, cfg.d_model)) * 0.02}
        tokens, caches = s["serve_step"](
            s["sp"], s["io"], caches, batch, jnp.asarray(pos, jnp.int32))
        seqs.append(np.asarray(tokens))
    dt = time.time() - t0
    out = np.stack(seqs, 1)
    print(f"decoded {args.tokens} tokens × batch {args.batch} in {dt:.2f}s "
          f"({args.batch * args.tokens / dt:.1f} tok/s)")
    for row in out[:4]:
        print("  ", row.tolist())


if __name__ == "__main__":
    main()
