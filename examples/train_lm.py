"""End-to-end driver: train a ~100M-param LM on a GVEL-loaded graph corpus.

The full pipeline the framework exists for: text edgelist --GVEL--> CSR
--random walks--> token batches --> train_step (AdamW, remat, ckpt).

  PYTHONPATH=src python examples/train_lm.py --steps 200
  (defaults are sized for CPU; --full-width uses the ~100M config)
"""
import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--full-width", action="store_true",
                   help="~100M params (slower on CPU)")
    p.add_argument("--ckpt-dir", default=None)
    args = p.parse_args()

    import dataclasses

    import jax

    from repro.configs import get_config, reduced_config
    from repro.core import read_csr, make_graph_file
    from repro.data.walks import walk_batch
    from repro.ft.coordinator import Coordinator, FTConfig
    from repro.models import init_params
    from repro.train import loop as train_loop
    from repro.train.optimizer import OptimizerConfig
    from repro.train.state import init_state
    from repro.train.step import make_train_step

    if args.full_width:
        # ~100M decoder: 12 x 768 with a 32k vocab
        cfg = dataclasses.replace(
            get_config("phi4-mini-3.8b"), num_layers=12, d_model=768,
            num_heads=12, num_kv_heads=4, head_dim=64, d_ff=3072,
            vocab_size=32768)
    else:
        cfg = reduced_config("phi4-mini-3.8b")

    tmp = tempfile.mkdtemp()
    path = os.path.join(tmp, "corpus.el")
    v, e = make_graph_file(path, "rmat", scale=13, edge_factor=16)
    t0 = time.perf_counter()
    csr = read_csr(path, num_vertices=v, engine="numpy")
    print(f"GVEL: loaded |V|={v:,} |E|={e:,} to CSR in "
          f"{time.perf_counter()-t0:.2f}s")

    params = init_params(jax.random.key(0), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {cfg.name} ({n_params/1e6:.1f}M params)")

    oc = OptimizerConfig(lr=3e-4, warmup_steps=20, decay_steps=args.steps)
    step = jax.jit(make_train_step(cfg, oc), donate_argnums=(0,))
    state = init_state(params)
    src = lambda i: walk_batch(csr, cfg, args.batch, args.seq, i)
    state, hist = train_loop.run(
        state, step, src, num_steps=args.steps, ckpt_dir=args.ckpt_dir,
        coordinator=Coordinator(FTConfig(ckpt_every=100)), log_every=20)
    print(f"loss: {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
