"""Train a small LM end-to-end on the AerialDB-backed data pipeline, with
checkpointing and a simulated restart (fault-tolerance path).

    PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""

import argparse
import time

import jax

from repro.configs.base import ModelConfig
from repro.data.pipeline import AerialPipeline, PipelineConfig
from repro.models.model import Model
from repro.train import checkpoint as ckpt
from repro.train import optimizer as optlib
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/aerialdb_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg = ModelConfig(name="lm-8m", family="dense", n_layers=4, d_model=128,
                      n_heads=4, n_kv=2, d_head=32, d_ff=512, vocab=512,
                      loss_chunk=512, attn_chunk_kv=64)
    model = Model(cfg)
    pipe = AerialPipeline(PipelineConfig(vocab=cfg.vocab, batch=8, seq=64))
    opt_cfg = optlib.OptConfig(lr=3e-3, warmup_steps=20,
                               total_steps=args.steps)

    params = model.init(jax.random.key(0))
    opt_state = optlib.init_opt_state(opt_cfg, params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {n_params/1e6:.1f}M params; data plane: AerialDB "
          f"({pipe.store_cfg.n_edges} edges, 3x replication)")

    @jax.jit
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        params, opt_state, m = optlib.adamw_update(opt_cfg, grads, opt_state,
                                                   params)
        return params, opt_state, loss

    start = 0
    if ckpt.latest_step(args.ckpt_dir) is not None:
        restored, start = ckpt.restore_checkpoint(
            args.ckpt_dir, {"params": params, "opt": opt_state})
        params, opt_state = restored["params"], restored["opt"]
        print(f"resumed from checkpoint at step {start}")

    t0 = time.time()
    for step in range(start, args.steps):
        batch = pipe.get_batch(step)      # deterministic in step => exact resume
        params, opt_state, loss = train_step(params, opt_state, batch)
        if (step + 1) % args.ckpt_every == 0 or step + 1 == args.steps:
            ckpt.save_checkpoint(args.ckpt_dir, step + 1,
                                 {"params": params, "opt": opt_state})
            print(f"step {step+1:4d} loss={float(loss):.4f} "
                  f"({(time.time()-t0)/(step-start+1)*1e3:.0f} ms/step) [ckpt]")
    print("done")


if __name__ == "__main__":
    enable_compile_cache()
    main()
