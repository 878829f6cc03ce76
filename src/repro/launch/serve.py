"""Batched serving driver: prefill a request batch, then decode N tokens.

Runs the SMOKE variant of any assigned architecture on CPU (the full
configs are exercised by the dry-run). Demonstrates the production decode
path: prefill -> KV cache -> serve_step (one token per call), with
continuous batching over a request queue.

    python -m repro.launch.serve --arch llama3.2-1b --requests 8 \\
        --prompt-len 64 --gen-len 32

``--net PRESET`` overlays a :mod:`repro.netsim` link model on the served
traffic and turns the final line into an SLO report: request/response
bytes flow through the preset's latency/bandwidth cost model into a
:class:`repro.comm.CommLog` (the same accounting the training benchmarks
use), which reports simulated network hours (``total_hours``) and
simulated seconds to drain 50% / 100% of the request queue
(``seconds_to_target``).

``--trace-jsonl PATH`` attaches a :class:`repro.obs.Tracer` through the
SAME JSONL sink format the training drivers use: per-batch ``prefill`` /
``decode`` spans, ``queue.wait`` events (how long each batch's requests
sat in the queue before being scheduled) and a final ``slo`` event, so
serving traces and training traces can be read with one
:func:`repro.obs.read_jsonl` and joined on ``type``/``name``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as _configs  # noqa: F401
from repro import netsim
from repro.comm import CommLog
from repro.models import api, transformer
from repro.models.base import get_config, list_archs
from repro.obs.trace import span

TOKEN_BYTES = 4  # int32 token ids on the wire


def make_requests(rng, n, prompt_len, vocab):
    return [rng.integers(1, vocab, size=(rng.integers(
        prompt_len // 2, prompt_len + 1),)).astype(np.int32)
        for _ in range(n)]


def wire_params(net) -> tuple:
    """Scalar ``(latency_s, bandwidth_bps)`` for the client link: tiered
    presets (``net.classes``) serve at their WORST link class — clients
    are the edge devices — everything else at the uniform scalars (which
    tiered presets leave at core defaults, so using them would silently
    report an all-core SLO)."""
    if net.classes is None:
        return net.latency_s, net.bandwidth_bps
    cl = net.classes
    return (max(cl.core_latency_s, cl.edge_latency_s),
            min(cl.core_bandwidth_bps, cl.edge_bandwidth_bps))


def batch_net_seconds(net, prompt_bytes: float, gen_len: int,
                      response_bytes: float) -> float:
    """Simulated network seconds for one served batch: the prompts arrive
    in one transfer, then each decoded token streams back to its client —
    one latency hit per step plus serialization of the full response."""
    lat, bw = wire_params(net)
    upload = lat + 8.0 * prompt_bytes / bw
    stream = gen_len * lat + 8.0 * response_bytes / bw
    return float(upload + stream)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3.2-1b", choices=list_archs())
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--net", default=None,
                    choices=sorted(netsim.PRESETS),
                    help="netsim preset overlay: report simulated network "
                         "time (CommLog total_hours / seconds_to_target) "
                         "next to the real tok/s")
    ap.add_argument("--trace-jsonl", default=None,
                    help="write repro.obs tracer spans (prefill / decode / "
                         "queue.wait / slo) to this JSONL file")
    args = ap.parse_args(argv)

    tracer = None
    if args.trace_jsonl:
        from repro.obs import JsonlSink, Tracer
        tracer = Tracer(sink=JsonlSink(args.trace_jsonl))

    cfg = get_config(args.arch, smoke=True)
    if cfg.encoder_layers > 0:
        raise SystemExit("enc-dec serving: use examples/serve_batched.py "
                         "(audio frontend is stubbed)")
    key = jax.random.PRNGKey(args.seed)
    params = api.init_params(cfg, key)
    rng = np.random.default_rng(args.seed)
    queue = make_requests(rng, args.requests, args.prompt_len, cfg.vocab_size)

    pad_to = args.prompt_len
    cache_len = transformer.cache_physical_len(
        cfg, args.prompt_len + args.gen_len)

    @jax.jit
    def prefill_fn(params, tokens):
        return transformer.prefill(cfg, params, tokens,
                                   cache_extra=cache_len - tokens.shape[1])

    @jax.jit
    def decode_fn(params, cache, tokens, pos):
        return transformer.decode_step(cfg, params, cache, tokens, pos)

    net = netsim.NetworkConfig.preset(args.net) if args.net else None
    comm = CommLog()
    n_requests = len(queue)

    t0 = time.time()
    done = 0
    batch_no = 0
    while queue:
        if tracer is not None:
            # queue wait: every request arrived at t0, so a batch's wait
            # is simply how long serving the earlier batches took
            tracer.event("queue.wait", batch=batch_no,
                         wait_s=time.time() - t0,
                         queued=len(queue))
        batch_reqs = [queue.pop(0) for _ in range(min(args.batch, len(queue)))]
        b = len(batch_reqs)
        lens = np.array([len(r) for r in batch_reqs], np.int32)
        toks = np.zeros((b, pad_to), np.int32)
        for i, r in enumerate(batch_reqs):
            toks[i, :len(r)] = r

        with span(tracer, "prefill", batch=batch_no, size=b):
            logits, cache = prefill_fn(params, jnp.asarray(toks))
            # sample the first token inside the span so it absorbs the
            # prefill compute (dispatch is async; argmax forces it)
            last = jnp.argmax(logits, -1).astype(jnp.int32)
            last.block_until_ready()
        out_tokens = np.zeros((b, args.gen_len), np.int32)
        pos = jnp.asarray(lens)  # next position per request
        # greedy (or sampled) continuation
        with span(tracer, "decode", batch=batch_no, size=b,
                  steps=args.gen_len):
            for t in range(args.gen_len):
                out_tokens[:, t] = np.asarray(last)
                logits, cache = decode_fn(params, cache, last[:, None], pos)
                if args.temperature > 0:
                    key_t = jax.random.fold_in(key, t)
                    last = jax.random.categorical(
                        key_t, logits / args.temperature).astype(jnp.int32)
                else:
                    last = jnp.argmax(logits, -1).astype(jnp.int32)
                pos = pos + 1
        done += b
        batch_no += 1
        if net is not None:
            # SLO accounting: prompts in + streamed tokens out, through
            # the preset's latency/bandwidth model; "accuracy" is the
            # drained fraction of the queue, so seconds_to_target(f) is
            # the simulated time to serve fraction f of the requests
            prompt_bytes = float(lens.sum()) * TOKEN_BYTES
            response_bytes = float(b * args.gen_len) * TOKEN_BYTES
            comm.record(batch_no, prompt_bytes + response_bytes,
                        acc=done / n_requests,
                        round_s=batch_net_seconds(net, prompt_bytes,
                                                  args.gen_len,
                                                  response_bytes))
        print(f"batch of {b}: prompts {lens.tolist()} -> "
              f"{args.gen_len} tokens each "
              f"(first req head: {out_tokens[0, :8].tolist()})", flush=True)

    dt = time.time() - t0
    total_tok = done * args.gen_len
    print(f"served {done} requests, {total_tok} tokens "
          f"in {dt:.1f}s = {total_tok / dt:.1f} tok/s")
    if net is not None:
        half = comm.seconds_to_target(0.5)
        full = comm.seconds_to_target(1.0)

        def _drain(v):  # None = that drain fraction was never reached
            return "not reached" if v is None else f"{v:.3f}s"

        print(f"SLO [{net.name}]: {comm.total_hours * 3600:.3f} simulated "
              f"network seconds total ({comm.total_hours:.6f} h, "
              f"{comm.total_gb * 1e3:.3f} MB on the wire); "
              f"p50 queue drain {_drain(half)}, full drain {_drain(full)}")
    if tracer is not None:
        tracer.event(
            "slo", requests=done, tokens=total_tok, wall_s=dt,
            tok_s=total_tok / dt,
            net=net.name if net is not None else None,
            sim_net_s=comm.total_hours * 3600 if net is not None else 0.0,
            rollup=tracer.rollup()["spans"])
        tracer.sink.close()
        print(f"trace: {tracer.sink.n_emitted} records -> "
              f"{tracer.sink.path}")


if __name__ == "__main__":
    main()
