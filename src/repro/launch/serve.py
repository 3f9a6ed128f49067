"""Serving launcher: the CF recommendation service (the paper's system) or
an LM decode service, on a chosen mesh or single host.

  python -m repro.launch.serve --service cf --users 2000 --items 800
  python -m repro.launch.serve --service lm --arch gemma3-1b --n-new 16
"""
from __future__ import annotations

import argparse
import logging

import numpy as np

from repro.launch.compile_cache import enable_compile_cache

log = logging.getLogger("repro.launch.serve")


def serve_cf(args) -> None:
    from repro.data import plant_twins, synth_ratings
    from repro.serving import CFServer, ServerConfig
    R = synth_ratings(0, args.users, args.items, args.users * 45)
    srv = CFServer(R, ServerConfig(capacity_extra=args.capacity,
                                   c_probes=args.probes))
    log.info("CF service up: %d users, %d items", args.users, args.items)
    burst = plant_twins(R, 8, source_user=3)
    for i in range(8):
        res = srv.onboard_user(burst[i])
        log.info("onboard %d twin=%s %.1fms", res.user_id, res.twin_found,
                 res.latency_ms)
    log.info("stats: %s", srv.stats.summary())


def serve_lm(args) -> None:
    import dataclasses
    import jax
    from repro.configs import get_arch
    from repro.models import transformer as lm
    from repro.serving import LMServer
    spec = get_arch(args.arch)
    cfg = dataclasses.replace(spec.config, n_layers=2, d_model=128,
                              n_heads=4, n_kv_heads=1, head_dim=32,
                              d_ff=256, vocab_size=1024,
                              window=(64 if spec.config.window else None))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    srv = LMServer(params, cfg, max_len=128)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)
    batch = prompts[[0, 1, 0, 1, 0]]
    out, info = srv.generate(batch, n_new=args.n_new)
    log.info("generated %s; dedup savings %.0f%% (prefilled %d/%d rows)",
             out.shape, 100 * info["dedup_savings"], info["prefill_rows"],
             info["batch"])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--service", choices=["cf", "lm"], default="cf")
    ap.add_argument("--users", type=int, default=2000)
    ap.add_argument("--items", type=int, default=800)
    ap.add_argument("--capacity", type=int, default=64)
    ap.add_argument("--probes", type=int, default=8)
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--n-new", type=int, default=8)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()
    (serve_cf if args.service == "cf" else serve_lm)(args)


if __name__ == "__main__":
    main()
