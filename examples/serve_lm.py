"""Early-exit greedy decoding from the transformer family's global model.

The serving-side payoff of depth-prefix training (docs/FAMILIES.md): the
SAME parameter tree answers at any of its exits, so a battery-poor device
decodes from exit 0 while a charged one uses the full depth — no
re-download, no distillation.

    PYTHONPATH=src python examples/serve_lm.py --gen 24
    PYTHONPATH=src python examples/serve_lm.py --exit 0 --gen 24 \
        --ckpt /tmp/lm.msgpack       # params saved by examples/train_lm.py

Decoding recomputes the full context window each step (the family's
training forward, ``seq``-token sliding window) — honest about what the
FL-scale model is; KV-cache serving is the big-LM stack's job, not this
demo's.  Without ``--ckpt`` the script first runs a few local DR-FL
rounds so the decode has a trained tree to exercise.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.family import get_family


def greedy_decode(fam, params, prompt, gen, exit_idx, seq):
    """Greedy next-token loop over a sliding ``seq``-token window."""
    toks = list(map(int, prompt))
    for _ in range(gen):
        window = jnp.asarray(toks[-seq:], jnp.int32)[None, :]
        logits = fam.apply_all_exits(params, window)[exit_idx]
        toks.append(int(jnp.argmax(logits[0])))
    return toks[len(prompt):]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", default=None,
                    help="msgpack params from examples/train_lm.py")
    ap.add_argument("--exit", dest="exit_idx", type=int, default=-1,
                    help="exit head to decode from (-1 = deepest)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seq", type=int, default=8)
    ap.add_argument("--width", type=float, default=0.25)
    ap.add_argument("--train-rounds", type=int, default=6,
                    help="warmup DR-FL rounds when no --ckpt is given")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    fam = get_family("transformer")
    M = fam.num_submodels()
    exit_idx = args.exit_idx % M
    params = fam.init(jax.random.PRNGKey(args.seed), 10,
                      width_mult=args.width, hw=args.seq)
    if args.ckpt:
        from repro.checkpoint import load_pytree
        params = load_pytree(args.ckpt, params)
        print("loaded", args.ckpt)
    else:
        print(f"no --ckpt: {args.train_rounds} local DR-FL warmup rounds")
        x, y = fam.make_dataset(1200, 10, hw=args.seq, noise=1.0,
                                seed=args.seed)
        g = params
        for rnd in range(args.train_rounds):
            d, loss = fam.client_update("drfl", g, M - 1, x, y, epochs=1,
                                        batch=32, lr=0.05,
                                        seed=args.seed + rnd)
            g = jax.tree.map(lambda a, b: a + b, g, d)
            print(f"  round {rnd} loss={float(loss):.3f}")
        params = g

    # prompt: a fresh window from the same Markov stream (held-out offset)
    x_eval, _ = fam.make_dataset(64, 10, hw=args.seq, noise=0.0,
                                 seed=args.seed + 1)
    prompt = np.asarray(x_eval[0])
    print(f"prompt tokens: {prompt.tolist()}")

    for m in sorted({0, exit_idx, M - 1}):
        t0 = time.time()
        out = greedy_decode(fam, params, prompt, args.gen, m, args.seq)
        dt = (time.time() - t0) / args.gen * 1000
        marker = " <-- --exit" if m == exit_idx else ""
        print(f"exit {m} ({m + 1}/{M} blocks): {out}  "
              f"[{dt:.1f} ms/token]{marker}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
