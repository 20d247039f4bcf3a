"""Train a ~100M llama-family model for a few hundred steps on synthetic LM
data, checkpointing at the end.

Defaults to a 115M config (12L, d=768) at seq 512 -- a few hundred steps run
in tens of minutes on CPU; use --tiny for a smoke-scale run (~1 minute).

Run: PYTHONPATH=src python examples/train_small.py [--steps N] [--tiny]
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.configs import get_config  # noqa: E402
from repro.jit_cache import enable_compile_cache  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.training import (  # noqa: E402
    AdamWConfig,
    DataConfig,
    TrainConfig,
    make_dataset,
    save_checkpoint,
    train,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--out", default="/tmp/skymemory_train_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    base = get_config("skymemory-tinyllama")
    if args.tiny:
        cfg = base.replace(num_layers=2, d_model=256, num_heads=4,
                           num_kv_heads=2, head_dim=64, d_ff=512,
                           vocab_size=2048, dtype="float32")
        args.steps = min(args.steps, 60)
        args.seq = 128
    else:
        # ~115M params: 12L x d768
        cfg = base.replace(num_layers=12, d_model=768, num_heads=12,
                           num_kv_heads=4, head_dim=64, d_ff=2048,
                           vocab_size=32000, dtype="float32")
    model = Model(cfg)
    print(f"training {cfg.param_count()/1e6:.0f}M params "
          f"for {args.steps} steps (seq={args.seq}, batch={args.batch})")

    ds = make_dataset(DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                                 batch_size=args.batch))
    tcfg = TrainConfig(
        opt=AdamWConfig(lr=6e-4, warmup_steps=max(args.steps // 20, 5),
                        total_steps=args.steps),
        remat=None,
        log_every=max(args.steps // 15, 1),
    )
    params, opt, hist = train(
        model, ds, tcfg, num_steps=args.steps,
        log_fn=lambda s, m: print(
            f"  step {s:4d}  loss={m['loss']:.4f} ce={m['ce']:.4f} "
            f"lr={m['lr']:.2e} gnorm={m['grad_norm']:.2f} "
            f"({m['elapsed_s']:.0f}s)"
        ),
    )
    assert hist[-1]["ce"] < hist[0]["ce"], "loss should decrease"
    save_checkpoint(args.out, params, opt, step=args.steps,
                    metadata={"arch": cfg.name})
    print(f"checkpoint written to {args.out}")


if __name__ == "__main__":
    main()
