"""End-to-end serving driver: batched autoregressive decode with the
KV/SSM cache machinery, requests scheduled through the WUKONG engine.

Each request batch is a DAG: prefill (token-by-token cache warmup on the
decode path) -> N decode steps -> detokenize stub. The engine gives us
retry-on-failure per request and concurrency across request batches.

    PYTHONPATH=src python examples/serve_lm.py --arch mixtral_8x7b \
        --requests 4 --prompt-len 16 --gen-len 24
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.core import EngineConfig, FaultConfig, GraphBuilder, WukongEngine
from repro.models import model as M
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.serve import build_serve_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral_8x7b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2,
                    help="sequences per request batch")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced(get_config(args.arch))
    params, _ = M.init_model(jax.random.PRNGKey(0), cfg)
    serve_step = jax.jit(build_serve_step(cfg))
    max_len = args.prompt_len + args.gen_len

    def handle_request(rid: int):
        """One batched request: greedy decode after prompt ingestion."""
        key = jax.random.PRNGKey(100 + rid)
        prompt = jax.random.randint(
            key, (args.batch, args.prompt_len), 0, cfg.vocab)
        cache = M.init_cache(cfg, args.batch, max_len)
        tok = prompt[:, 0]
        t0 = time.time()
        generated = []
        for pos in range(max_len - 1):
            logits, cache = serve_step(
                params, cache, {"token": tok, "pos": jnp.int32(pos)})
            if pos + 1 < args.prompt_len:
                tok = prompt[:, pos + 1]            # prefill phase
            else:
                tok = jnp.argmax(logits, axis=-1)   # greedy decode
                generated.append(np.asarray(tok))
        dt = time.time() - t0
        gen = np.stack(generated, axis=1)
        return {
            "rid": rid,
            "tokens": gen,
            "decode_tps": args.batch * gen.shape[1] / dt,
            "latency_s": dt,
        }

    # Requests as a WUKONG DAG: fan-out of independent request handlers
    # into a summary fan-in (engine supplies retry + concurrency).
    g = GraphBuilder()
    reqs = [g.add(lambda r=r: handle_request(r), name=f"request-{r}")
            for r in range(args.requests)]
    g.add(lambda *rs: {
        "n": len(rs),
        "mean_tps": float(np.mean([r["decode_tps"] for r in rs])),
        "p99_latency_s": float(np.percentile(
            [r["latency_s"] for r in rs], 99)),
    }, *reqs, name="summary")

    eng = WukongEngine(EngineConfig(
        faults=FaultConfig(task_failure_prob=0.05, max_retries=2, seed=3),
        job_timeout_s=3600.0))
    t0 = time.time()
    rep = eng.compute(g.build())
    summary = rep.results["summary"]
    print(f"arch={cfg.name} requests={args.requests} "
          f"batch={args.batch} gen={args.gen_len}")
    print(f"served in {time.time() - t0:.1f}s  "
          f"mean decode throughput {summary['mean_tps']:.1f} tok/s  "
          f"p99 latency {summary['p99_latency_s']:.2f}s")
    r0 = rep.results["request-0"]
    print("sample continuation (req 0, seq 0):",
          r0["tokens"][0][:12].tolist())


if __name__ == "__main__":
    main()
