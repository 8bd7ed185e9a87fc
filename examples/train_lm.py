"""End-to-end LM training driver: data pipeline -> train_step -> fault-
tolerant loop (checkpoint/restart, straggler detection, failure retry).

Defaults to a ~6M-parameter model so it runs on the CPU container in a few
minutes; ``--preset 100m --steps 300`` is the full-size driver on real
hardware. Kill it mid-run and start it again: it restores the latest
checkpoint and the stateless data pipeline resumes bit-exactly.

    PYTHONPATH=src python examples/train_lm.py --steps 30
    PYTHONPATH=src python examples/train_lm.py --steps 30   # -> restarts
    PYTHONPATH=src python examples/train_lm.py --inject-failure 7
"""
import argparse

import jax

from repro.data import DataConfig, SyntheticLMDataset
from repro.models import ModelConfig, init_params
from repro.optim import adamw_init
from repro.optim.adamw import AdamWConfig
from repro.train import TrainLoopConfig, fault_tolerant_train, make_train_step

PRESETS = {
    # ~6M params: CPU-friendly end-to-end demo
    "6m": dict(n_layers=4, d_model=256, n_heads=4, n_kv=2, d_head=64,
               d_ff=1024, vocab=8192, seq=256, batch=8),
    # ~19M params
    "20m": dict(n_layers=6, d_model=384, n_heads=6, n_kv=2, d_head=64,
                d_ff=1536, vocab=16384, seq=512, batch=16),
    # ~100M params: the deliverable-scale driver (run on real hardware)
    "100m": dict(n_layers=12, d_model=768, n_heads=12, n_kv=4, d_head=64,
                 d_ff=2048, vocab=32768, seq=1024, batch=32),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="6m", choices=sorted(PRESETS))
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--inject-failure", type=int, default=None,
                    help="simulate a device loss at this step (recovers "
                    "from checkpoint)")
    args = ap.parse_args()

    p = PRESETS[args.preset]
    cfg = ModelConfig(
        name=f"demo-{args.preset}", family="dense", n_layers=p["n_layers"],
        d_model=p["d_model"], n_heads=p["n_heads"], n_kv=p["n_kv"],
        d_head=p["d_head"], d_ff=p["d_ff"], vocab=p["vocab"], act="swiglu",
        qk_norm=True, tie_embeddings=True, attn_q_chunk=128,
        attn_kv_chunk=128, loss_chunk=256)
    print(f"model: {cfg.name}  params={cfg.n_params / 1e6:.1f}M  "
          f"seq={p['seq']} batch={p['batch']}")

    data = SyntheticLMDataset(DataConfig(
        vocab=cfg.vocab, seq_len=p["seq"], global_batch=p["batch"]))

    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_state = adamw_init(params)
    step_fn = jax.jit(make_train_step(
        cfg, AdamWConfig(lr=3e-4, total_steps=args.steps),
        microbatches=args.microbatches), donate_argnums=(0, 1))

    fails = {args.inject_failure} if args.inject_failure is not None else set()

    def failure_hook(s):
        if s in fails:
            fails.remove(s)     # fail once, then recover
            raise RuntimeError(f"injected device loss at step {s}")

    def log(msg):
        print(msg, flush=True)

    loop_cfg = TrainLoopConfig(
        total_steps=args.steps, checkpoint_every=args.ckpt_every,
        checkpoint_dir=args.ckpt_dir)
    params, opt_state, events = fault_tolerant_train(
        loop_cfg, step_fn, (params, opt_state), iter(data), data.batch_at,
        failure_hook=failure_hook, log=log)

    losses = events["losses"]
    k = max(1, len(losses) // 10)
    print(f"\nloss: first {sum(losses[:k]) / k:.4f} -> "
          f"last {sum(losses[-k:]) / k:.4f} over {len(losses)} steps")
    print(f"retries={events['retries']} stragglers={len(events['stragglers'])}")
    assert losses and losses[-1] < losses[0], "loss should decrease"
    print("done — checkpoints in", args.ckpt_dir)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
