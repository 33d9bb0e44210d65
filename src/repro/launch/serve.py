"""Serving launcher: ``python -m repro.launch.serve --arch <id>`` runs the
batched engine on a reduced config (CPU demo); the meshed decode step
(``train.step.make_serve_bundle``) compiles in ``tests/test_sharding_meshes.py``."""

from __future__ import annotations

import argparse

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="codeqwen1.5-7b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    args = ap.parse_args()

    from ..configs import get_config, reduced
    from ..models import get_api
    from ..parallel.spec import init_params
    from ..serve import Request, ServeEngine

    cfg = reduced(get_config(args.arch))
    api = get_api(cfg)
    params = init_params(api.param_specs(cfg), jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, max_len=args.max_len, slots=args.slots)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size, 4 + i % 6).astype(np.int32),
                    max_tokens=args.max_tokens) for i in range(args.requests)]
    import time
    t0 = time.perf_counter()
    done = engine.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in done)
    print(f"served {len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s, {cfg.name} reduced)")


if __name__ == "__main__":
    main()
