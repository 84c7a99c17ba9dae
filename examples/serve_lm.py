"""Serve a small LM with batched requests through the decode engine —
the serve_step path the decode_* dry-run cells lower at production scale.

    PYTHONPATH=src python examples/serve_lm.py
"""

import time

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.model import Model
from repro.serve.engine import Engine, ServeConfig
from repro.launch.compile_cache import enable_compile_cache


def main():
    cfg = ModelConfig(name="lm-serve", family="dense", n_layers=4, d_model=128,
                      n_heads=4, n_kv=2, d_head=32, d_ff=512, vocab=512,
                      attn_chunk_kv=64)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = Engine(model, params, ServeConfig(max_new_tokens=24, max_seq=128))

    rng = np.random.default_rng(0)
    batch = rng.integers(1, cfg.vocab, (8, 12)).astype(np.int32)  # 8 requests
    t0 = time.time()
    out = engine.generate(batch)
    dt = time.time() - t0
    n_tok = out.size
    print(f"served 8 requests x 24 new tokens in {dt:.2f}s "
          f"({n_tok/dt:.0f} tok/s on CPU)")
    print("sample continuation ids:", out[0][:12].tolist())


if __name__ == "__main__":
    enable_compile_cache()
    main()
