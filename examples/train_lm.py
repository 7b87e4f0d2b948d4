"""Train a ~10M-parameter LM for a few hundred steps on synthetic bigram
data, with async checkpointing and restart.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

(The assigned architectures are pod-scale; this uses a width-reduced qwen
config so the full loop — sharded step, checkpointing, metrics — runs on
CPU in minutes.  Loss falls well below the unigram entropy because the data
has learnable bigram structure.)
"""
import argparse
import dataclasses
import tempfile

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced
from repro.data import SyntheticTokens
from repro.train.loop import LoopConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = dataclasses.replace(
        reduced(get_config("qwen1.5-0.5b")),
        n_layers=4, d_model=256, n_heads=4, n_kv_heads=4, head_dim=64,
        d_ff=512, vocab_size=4096,
    )
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp()

    data = iter(SyntheticTokens(cfg.vocab_size, args.seq, args.batch, seed=0))
    trainer = Trainer(
        cfg,
        LoopConfig(steps=args.steps, ckpt_every=100, ckpt_dir=ckpt_dir,
                   lr=1e-3, log_every=20),
        data,
    )
    result = trainer.run()
    losses = [(m["step"], m["loss"]) for m in result["log"] if "loss" in m]
    for s, l in losses:
        print(f"step {s:4d}  loss {l:.4f}")
    first, last = losses[0][1], losses[-1][1]
    print(f"\nloss {first:.3f} → {last:.3f} over {result['final_step']} steps "
          f"({result['recoveries']} recoveries); checkpoints in {ckpt_dir}")
    assert last < first, "loss must decrease"


if __name__ == "__main__":
    main()
