"""End-to-end driver: train a ~100M-parameter granite-family LM for a few
hundred steps on the synthetic Markov stream, with checkpoint/restart.

    PYTHONPATH=src python examples/train_lm.py [--steps 300] [--d-model 512]

(~100M params with the defaults; use --smoke for a 30-second CI run.)
The loss must fall well below the ln(V) i.i.d. entropy — the stream's
token-transition structure is learnable (see repro/train/data.py).
"""
import argparse

from repro.configs import get_config
from repro.models.registry import get_model
from repro.models.module import count_params
from repro.models import module
from repro.train.data import TokenStream
from repro.train.loop import TrainConfig, train

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=32_000)
    ap.add_argument("--ckpt-dir", default="ckpts/train_lm")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    if args.smoke:
        args.d_model, args.layers, args.vocab = 64, 2, 512
        args.steps, args.seq = 40, 64

    cfg = get_config("granite-3-2b").replace(
        num_layers=args.layers, d_model=args.d_model,
        n_heads=max(args.d_model // 64, 1),
        n_kv_heads=max(args.d_model // 128, 1),
        d_ff=4 * args.d_model, vocab=args.vocab, dtype="float32")
    model = get_model(cfg)
    values, _ = module.split(model.init(jax.random.PRNGKey(0)))
    n = count_params(values)
    print(f"model: {cfg.num_layers}L d={cfg.d_model} vocab={cfg.vocab} "
          f"-> {n / 1e6:.1f}M params")

    stream = TokenStream(cfg, batch=args.batch, seq=args.seq, seed=0)
    tc = TrainConfig(lr=3e-4 if n > 5e7 else 3e-3,
                     warmup_steps=max(args.steps // 20, 5),
                     total_steps=args.steps)
    state = train(model, tc, stream, steps=args.steps,
                  checkpoint_dir=args.ckpt_dir, checkpoint_every=100,
                  log_every=10)
    eval_batch = stream.batch_at(10_000)
    loss = float(model.loss(state.params, eval_batch)[0])
    import math
    print(f"final eval loss {loss:.4f} (iid-entropy ceiling "
          f"{math.log(cfg.vocab):.2f})")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
