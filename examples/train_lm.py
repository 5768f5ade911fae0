"""End-to-end driver: train a ~100M-param llama-family model for a few
hundred steps on the synthetic pipeline with the full production substrate
(supervisor, async checkpoints, straggler tracking), then sample from it.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]
(~100M params; on this CPU container a step takes a few seconds — use
--small for a quick pass.)
"""
import argparse
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import Prefetcher, SyntheticLM
from repro.models import ModelConfig, count_params, init_params
from repro.optim import adamw
from repro.parallel.ctx import NO_PARALLEL as ctx
from repro.runtime import Supervisor, SupervisorConfig
from repro.runtime.compile_cache import use_compile_cache
from repro.serving import Engine
from repro.train import make_train_step


def model_100m():
    return ModelConfig(
        name="llama-100m", family="dense", num_layers=8, d_model=640,
        num_heads=10, num_kv_heads=5, d_ff=1792, vocab_size=32000,
        head_dim=64, tie_embeddings=True, attn_chunk=256, logit_chunk=256)


def model_small():
    return ModelConfig(
        name="llama-8m", family="dense", num_layers=4, d_model=192,
        num_heads=6, num_kv_heads=2, d_ff=512, vocab_size=2048,
        head_dim=32, tie_embeddings=True, attn_chunk=64, logit_chunk=64)


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--small", action="store_true")
    args = ap.parse_args()

    cfg = model_small() if args.small else model_100m()
    print(f"model {cfg.name}: {count_params(cfg) / 1e6:.1f}M params, "
          f"{args.steps} steps @ {args.batch}x{args.seq}")

    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw.init(params)
    ocfg = adamw.AdamWConfig(lr=6e-4, warmup_steps=30,
                             total_steps=args.steps)
    step_fn = jax.jit(make_train_step(cfg, ctx, ocfg))
    data = SyntheticLM(cfg.vocab_size, args.batch, args.seq, seed=0)

    ckpt_dir = os.path.join(tempfile.gettempdir(), "repro_train_lm")
    sup = Supervisor(SupervisorConfig(ckpt_dir=ckpt_dir, ckpt_every=100),
                     step_fn, Prefetcher(data), params, opt)

    def log(step, metrics, dt):
        if step % 20 == 0 or step in (1, 5, 10):
            print(f"step {step:4d}  loss {float(metrics['loss']):.4f}  "
                  f"lr {float(metrics['lr']):.2e}  {dt:.2f}s/step",
                  flush=True)

    params, _ = sup.run(args.steps, metrics_cb=log)
    print(f"training done (restarts={sup.restarts}, "
          f"stragglers={len(sup.stragglers)})")

    # sample: the model should reproduce codebook n-grams far above chance
    eng = Engine(cfg, params, max_len=96)
    prompt_full = data.batch_at(10_001)["tokens"][:2, :32]
    prompt = jnp.asarray(prompt_full[:, :16], jnp.int32)
    gen = eng.generate(prompt, max_new_tokens=16)
    cont = np.asarray(gen)
    match = (cont[:, :16] == prompt_full[:, 16:32]).mean()
    print(f"greedy continuation matches held-out stream at "
          f"{match * 100:.0f}% of positions (noise floor "
          f"{100.0 / cfg.vocab_size:.2f}%)")


if __name__ == "__main__":
    main()
