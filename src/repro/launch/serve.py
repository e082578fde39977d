"""Batched serving loop with streaming PLA KV-cache compression.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
        --prompt-len 128 --gen 32 [--pla-kv --kv-hot 64 --kv-chunk 32]

``--no-smoke`` disables the shrunk config (the old ``--smoke`` flag
defaulted on and could never be turned off from the CLI).

Fleet-serving mode (paper scenario 1, ROADMAP "Million-stream serving
front-end") drives the admission-controlled front-end instead of the KV
demo — churny synthetic sensors through :class:`repro.serving.ServeLoop`
with an optional fleet-wide egress budget:

    PYTHONPATH=src python -m repro.launch.serve --fleet \
        --fleet-streams 32 --fleet-ticks 60 --churn 0.1 \
        --budget-bytes-per-s 2000

Prefills a batch of synthetic prompts, then decodes.  With ``--pla-kv``,
KV tokens are compressed *as they cross the hot window* (paper scenario
2): every ``--kv-chunk`` prefill steps the newly cold token columns of
each layer are pushed through a :class:`StreamingKVCompressor`, which
segments them incrementally through the carry-state engine and pops a
finished :class:`CompressedKVBlock` every 256 tokens — no one-shot
re-compression loop at the end of prefill.  Decode then runs against the
reconstructed history, and the run reports storage savings plus the
worst K/V perturbation vs. the exact cache.
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.compression.kv_cache import (PLAKVConfig, StreamingKVCompressor,
                                        compressed_block_stats,
                                        decompress_kv_block)
from repro.configs import ALIASES, get_config
from repro.launch.specs import demo_batch
from repro.models.zoo import build_model


def _push_cold(comps, blocks, cache, lo: int, hi: int) -> None:
    """Feed cache token columns [lo, hi) of every layer to its compressor."""
    for layer, comp in enumerate(comps):
        blocks[layer].extend(comp.push(cache.k[layer, :, lo:hi],
                                       cache.v[layer, :, lo:hi]))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    # BooleanOptionalAction so --no-smoke actually exists: the old
    # ``action="store_true", default=True`` spelling made smoke mode
    # impossible to disable from the CLI.
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="shrunk model config (use --no-smoke for full)")
    ap.add_argument("--arch", default="yi-6b", choices=list(ALIASES))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--pla-kv", action="store_true")
    ap.add_argument("--kv-eps", type=float, default=0.1)
    ap.add_argument("--kv-hot", type=int, default=64,
                    help="hot window: most recent tokens kept raw")
    ap.add_argument("--kv-chunk", type=int, default=32,
                    help="push cold tokens to the compressor every N steps")
    # Fleet-serving mode (repro.serving).
    ap.add_argument("--fleet", action="store_true",
                    help="serve a churny synthetic sensor fleet instead "
                         "of the KV demo")
    ap.add_argument("--fleet-streams", type=int, default=32,
                    help="live streams held in the slot plane")
    ap.add_argument("--fleet-capacity", type=int, default=0,
                    help="slot capacity (0: 2x the live streams)")
    ap.add_argument("--fleet-ticks", type=int, default=60)
    ap.add_argument("--tick-width", type=int, default=64)
    ap.add_argument("--churn", type=float, default=0.1,
                    help="fraction of live streams replaced per tick")
    ap.add_argument("--method", default="linear")
    ap.add_argument("--protocol", default="singlestream")
    ap.add_argument("--eps", type=float, default=0.5)
    ap.add_argument("--budget-bytes-per-s", type=float, default=0.0,
                    help="fleet egress budget (0: fixed eps, no "
                         "controller)")
    return ap


def serve_fleet(args) -> None:
    """Churny synthetic fleet through the admission-controlled loop."""
    import numpy as np

    from repro.serving import GlobalEpsBudget, ServeLoop, SlotManager

    rng = np.random.default_rng(0)
    cap = args.fleet_capacity or 2 * args.fleet_streams
    budget = None
    if args.budget_bytes_per_s > 0:
        budget = GlobalEpsBudget(args.budget_bytes_per_s,
                                 sample_hz=float(args.tick_width))
    mgr = SlotManager(args.method, args.protocol, capacity=cap,
                      eps0=args.eps)
    loop = ServeLoop(mgr, tick_width=args.tick_width,
                     queue_cap=8 * args.tick_width, budget=budget)

    def fresh(name):
        loop.admit(name, eps=args.eps)

    n_admitted = 0
    live = []
    for _ in range(args.fleet_streams):
        fresh(f"sensor-{n_admitted}")
        live.append(f"sensor-{n_admitted}")
        n_admitted += 1
    t0 = time.time()
    total_bytes = total_points = 0
    for k in range(args.fleet_ticks):
        # churn: replace a fraction of the fleet, out of phase
        for _ in range(int(len(live) * args.churn)):
            gone = live.pop(int(rng.integers(len(live))))
            rep = loop.evict(gone)
            total_bytes += len(rep.tail) \
                + sum(len(b) for _, _, b in rep.wire)
            fresh(f"sensor-{n_admitted}")
            live.append(f"sensor-{n_admitted}")
            n_admitted += 1
        for name in live:
            loop.offer(name, rng.normal(0, 1, args.tick_width)
                       .astype(np.float32).cumsum())
        rep = loop.tick()
        total_bytes += rep.nbytes
        total_points += rep.consumed
        if k % 10 == 0 or k == args.fleet_ticks - 1:
            pool = (f" pool={rep.budget_pool:.0f}B"
                    if rep.budget_pool is not None else "")
            print(f"tick {rep.tick:4d}: live={rep.live} "
                  f"consumed={rep.consumed} bytes={rep.nbytes} "
                  f"eps=[{rep.eps_lo:.3g}, {rep.eps_hi:.3g}]"
                  f"{pool} shed={rep.shed_total} "
                  f"wait={1e3 * rep.wait_mean_s:.2f}ms "
                  f"(max {1e3 * rep.wait_max_s:.2f}ms)")
    dt_s = time.time() - t0
    print(f"served {total_points} points / {total_bytes} wire bytes "
          f"across {n_admitted} stream admissions in {dt_s:.2f}s "
          f"({total_points / max(dt_s, 1e-9):,.0f} pts/s)")


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.fleet:
        serve_fleet(args)
        return

    cfg = get_config(args.arch, smoke=args.smoke)
    api = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = api.init(key)
    batch = demo_batch(cfg, B=args.batch, T=args.prompt_len, key=key)
    max_len = args.prompt_len + args.gen
    cache = api.make_cache(params, batch, max_len)

    pla_on = args.pla_kv and hasattr(cache, "k")
    kcfg = PLAKVConfig(block=256, eps=args.kv_eps)
    if pla_on:
        n_layers = cache.k.shape[0]
        comps = [StreamingKVCompressor(kcfg) for _ in range(n_layers)]
        blocks = [[] for _ in range(n_layers)]
        pushed = 0

    decode = jax.jit(lambda p, t, c: api.decode(p, t, c))
    t0 = time.time()
    for i in range(args.prompt_len):
        logits, cache = decode(params, batch["tokens"][:, i:i + 1], cache)
        if pla_on:
            cold_end = i + 1 - args.kv_hot
            if cold_end - pushed >= args.kv_chunk:
                _push_cold(comps, blocks, cache, pushed, cold_end)
                pushed = cold_end
    prefill_s = time.time() - t0

    if pla_on:
        # Tokens that crossed the hot window by the end of prefill.
        cold_end = max(args.prompt_len - args.kv_hot, 0)
        if cold_end > pushed:
            _push_cold(comps, blocks, cache, pushed, cold_end)
            pushed = cold_end
        n_blocks = len(blocks[0]) if blocks else 0
        if n_blocks:
            tot_raw = tot_comp = 0
            max_err = 0.0
            kd_layers, vd_layers = [], []
            for layer, layer_blocks in enumerate(blocks):
                kds, vds = [], []
                for b, blk in enumerate(layer_blocks):
                    lo, hi = b * kcfg.block, (b + 1) * kcfg.block
                    st = compressed_block_stats(blk, kcfg)
                    tot_raw += st["raw_bytes"]
                    tot_comp += st["compressed_bytes"]
                    kd, vd = decompress_kv_block(blk, kcfg)
                    max_err = max(
                        max_err,
                        float(jnp.abs(kd - cache.k[layer, :, lo:hi]
                                      .astype(jnp.float32)).max()),
                        float(jnp.abs(vd - cache.v[layer, :, lo:hi]
                                      .astype(jnp.float32)).max()))
                    kds.append(kd)
                    vds.append(vd)
                kd_layers.append(jnp.concatenate(kds, axis=1))
                vd_layers.append(jnp.concatenate(vds, axis=1))
            # One scatter per tensor: .at[].set on the full (L,B,T,KH,hd)
            # cache copies it whole, so per-block writes would be O(L*B_n)
            # full-cache copies.
            hi = n_blocks * kcfg.block
            cache = type(cache)(
                cache.k.at[:, :, :hi].set(
                    jnp.stack(kd_layers).astype(cache.k.dtype)),
                cache.v.at[:, :, :hi].set(
                    jnp.stack(vd_layers).astype(cache.v.dtype)),
                cache.length)
            print(f"PLA KV (streaming): {n_blocks} cold block(s)/layer, "
                  f"{tot_comp} vs {tot_raw} raw bytes "
                  f"({tot_comp/tot_raw:.3f}x) at eps={kcfg.eps}, "
                  f"max |err|={max_err:.3g}; "
                  f"{comps[0].pending_tokens} tokens pending")
        else:
            print(f"PLA KV (streaming): no block completed "
                  f"(cold tokens={pushed} < block={kcfg.block}); "
                  f"{comps[0].pending_tokens} tokens pending")

    tok = batch["tokens"][:, -1:]
    t0 = time.time()
    out_tokens = []
    for _ in range(args.gen):
        logits, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out_tokens.append(tok)
    gen_s = time.time() - t0
    toks = jnp.concatenate(out_tokens, axis=1)
    print(f"prefill {args.prompt_len} toks x{args.batch}: {prefill_s:.2f}s "
          f"| decode {args.gen} toks: {gen_s:.2f}s "
          f"({args.gen*args.batch/gen_s:.1f} tok/s)")
    print("sample:", toks[0, :16].tolist())


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
