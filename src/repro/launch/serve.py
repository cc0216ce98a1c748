"""Serving launcher: load (or randomly init) a model and serve a batch of
synthetic requests through the engine, reporting tokens/sec and p95 TTFT,
and the engine's counters: chunk fill, queue-wait p90, host time per
tick, decode tokens per tick, slot resets and block-table uploads.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --reduced
"""
import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--cache-mode", default="auto",
                    choices=["auto", "paged", "dense", "legacy"],
                    help="paged = block-pool KV cache (default on "
                         "attention-only archs)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged mode)")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="pool size in blocks (0 = dense-equivalent)")
    ap.add_argument("--policy", default="conservative",
                    choices=["conservative", "mixed"],
                    help="tick policy: conservative keeps greedy decode "
                         "bit-stable; mixed packs decode into prefill "
                         "dispatches")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="mean request arrivals/sec (0 = all at once)")
    ap.add_argument("--max-steps", type=int, default=10_000)
    args = ap.parse_args()

    import jax
    import numpy as np
    from repro.configs.base import get_config, reduced
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import mesh_for_devices
    from repro.models.model import Model
    from repro.serve import Engine, Request

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = mesh_for_devices(len(jax.devices()))
    engine = Engine(cfg, mesh, slots=args.slots, max_len=args.max_len,
                    cache_mode=args.cache_mode,
                    block_size=args.block_size,
                    num_blocks=args.num_blocks or None,
                    policy=args.policy)
    model = Model(cfg, mesh)
    if args.ckpt:
        from repro.checkpoint import checkpoint as ck
        step = ck.latest_step(args.ckpt)
        tree = ck.restore(args.ckpt, step,
                          {"params": model.init_abstract()})
        params = tree["params"]
    else:
        params = model.init(jax.random.PRNGKey(0))
    engine.load(params)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(8, 64))),
                    max_new_tokens=args.new_tokens)
            for i in range(args.requests)]
    if args.rate > 0:
        gaps = rng.exponential(1.0 / args.rate, size=args.requests)
        arrivals = [float(t) for t in np.cumsum(gaps)]
    else:
        arrivals = [0.0] * args.requests
    results = engine.run_trace(reqs, arrivals, max_steps=args.max_steps)

    done_tokens = sum(len(v) for v in results.values())
    ttfts = sorted(m["ttft_s"] for m in results.metrics.values()
                   if m.get("ttft_s") is not None)
    elapsed = max((m.get("done_s", 0.0)
                   for m in results.metrics.values()), default=0.0)
    print(f"[serve] mode={engine.cache_mode} completed "
          f"{len(results)}/{args.requests} requests, {done_tokens} tokens")
    if ttfts and elapsed > 0:
        p95 = ttfts[min(len(ttfts) - 1, int(0.95 * len(ttfts)))]
        print(f"[serve] {done_tokens / elapsed:.0f} tok/s, "
              f"p95 TTFT {p95 * 1e3:.1f} ms")
    st = engine.stats
    if st.dispatched:
        waits = [m["admit_s"] - m["arrival_s"]
                 for m in results.metrics.values()]
        fill = 100.0 * st.chunk_tokens / st.chunk_rows if st.chunk_rows \
            else 0.0
        print(f"[serve] {st.dispatched} ticks {st.ticks}, chunk fill "
              f"{fill:.1f}%, queue wait p90 "
              f"{np.percentile(waits, 90) * 1e3:.1f} ms, host "
              f"{st.host_s / st.dispatched * 1e3:.2f} ms/tick")
        print(f"[serve] {st.decode_tokens} decode tokens "
              f"({st.decode_tokens / st.dispatched:.2f}/tick of "
              f"{engine.slots} slots), {st.resets} slot resets, "
              f"{st.btab_uploads} block-table uploads")
    if engine.pool is not None:
        print(f"[serve] pool high water {engine.pool.high_water}/"
              f"{engine.pool.num_blocks} blocks "
              f"({engine.pool.block_size} tokens each)")
    if results.truncated:
        unfinished = sorted(results.unfinished)
        raise SystemExit(
            f"[serve] TRUNCATED at --max-steps={args.max_steps}: "
            f"unfinished requests {unfinished}")


if __name__ == "__main__":
    main()
