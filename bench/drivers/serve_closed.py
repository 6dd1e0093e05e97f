"""Serving cells: a closed loop of clients over ``make_engine_group``.

Clients use the public API only: ``EventLoopGroup.submit`` and ``run``.
A client sends its next request when its last result comes back; ``run``
returns results when every loop has drained, so all clients of a round
send together. A request's latency is from its submission to the return
of its result through ``run``. The window closes with the first round
that returns after ``--seconds``: rates count all the work and all the
time of every round in it.

Set-up draws the weights on the chip and serves one warm-up round per
prefill shape the window meets (a wave pads its prompts to its longest;
a stream the window never draws), so that every program the window calls
is compiled before it opens.

After the window a sample of the finished requests, drawn from the seed
with the one with most generated tokens in it, is run through the plain
reference: for each prompt with its served tokens, ``logit_gap`` is the
widest gap by which a served (greedy) token's reference logit lies below
the reference's best at that position.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from bench import flops, harness, model, reference, traffic


class Session:
    """One engine group on the cell's chips."""

    def __init__(self, mc, cfg: dict, mix: dict, devs: list, seed: int):
        from repro.configs.base import CommConfig, ServeConfig
        from repro.serving import make_engine_group
        sv = mix["serve"]
        self.cfg, self.mix = cfg, mix
        serve = ServeConfig(
            event_loops=sv["event_loops"], max_batch=sv["max_batch"],
            max_len=sv["max_len"], poll=sv["poll"],
            comm=CommConfig(**sv["comm"]))
        mesh = jax.make_mesh((len(devs),), ("data",), devices=devs,
                             axis_types=(AxisType.Auto,))
        self.params = model.init_weights(cfg, seed)
        self.group = make_engine_group(mc, self.params, serve,
                                       mesh=mesh, seed=seed & 0x7FFFFFFF)
        self.uid = 0

    def round(self, reqs: list) -> tuple:
        """Serve one round [(prompt, max_new)]; returns (results by uid,
        requests by uid, seconds from submission to return)."""
        from repro.serving import Request
        batch = []
        for prompt, max_new in reqs:
            batch.append(Request(uid=self.uid, prompt=prompt,
                                 max_new=max_new, temperature=0.0))
            self.uid += 1
        t0 = time.perf_counter()
        self.group.submit(batch)
        results = self.group.run(threads=True)
        dt = time.perf_counter() - t0
        return ({r.uid: r for r in results}, {q.uid: q for q in batch}, dt)


_GAP_FNS: dict = {}


def _gaps_fn(cfg: dict, pick: str):
    """Jitted (params, seq (1, S), pos (T,), tok (T,)) -> gap per position:
    the reference's best logit at ``pos`` less that of the token taken
    there, the served one or (``pick="fp8"``) the fp8 control's first."""
    key = (model.items(cfg), pick)
    if key not in _GAP_FNS:
        @jax.jit
        def fn(params, seq, pos, tok):
            x = reference.hidden(params, seq, cfg)[0][pos]
            logits = reference.head(params, x, cfg)
            if pick == "fp8":
                x8 = reference.hidden(params, seq, cfg, "fp8")[0][pos]
                tok = jnp.argmax(reference.head(params, x8, cfg, "fp8"), -1)
            taken = jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]
            return jnp.max(logits, -1) - taken
        _GAP_FNS[key] = fn
    return _GAP_FNS[key]


def request_gap(fn, params, mix: dict, prompt, tokens) -> float:
    """Widest gap over the positions that produced ``tokens``. Every
    request runs at one shape: the sequence padded to ``max_len`` (causal,
    so padding after it changes nothing), the positions to the mix's
    longest output."""
    n, p = len(tokens), len(prompt)
    seq = np.zeros((1, mix["serve"]["max_len"]), np.int32)
    seq[0, :p] = prompt
    seq[0, p:p + n - 1] = tokens[:-1]
    width = mix["output"]["max"]
    pos = np.full((width,), p - 1, np.int32)
    pos[:n] = np.arange(p - 1, p - 1 + n)
    tok = np.zeros((width,), np.int32)
    tok[:n] = tokens
    gaps = fn(params, jnp.asarray(seq), jnp.asarray(pos), jnp.asarray(tok))
    return float(jnp.max(gaps[:n]))


def sample(done: dict, seed: int, n: int) -> list:
    """``n`` finished uids drawn from the seed, the one with the most
    generated tokens among them."""
    uids = sorted(done)
    longest = max(uids, key=lambda u: (len(done[u][1]), -u))
    rest = [u for u in uids if u != longest]
    rng = traffic.rng_for(seed, 4)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gap(cfg: dict, mix: dict, seed: int, done: dict,
                  pick: str = "served") -> float:
    """The widest logit gap over the sampled requests."""
    params = model.init_weights(cfg, seed)
    fn = _gaps_fn(cfg, pick)
    return max(request_gap(fn, params, mix, *done[u])
               for u in sample(done, seed, mix["check"]["requests"]))


def serve_window(sess: Session, ctx, tracer, counter) -> dict:
    """Rounds until one returns after the window's seconds."""
    lat, done, rounds = [], {}, 0
    attempted = failed = 0
    tokens = 0
    used_flops = 0.0
    with tracer.window():
        counter.active = True
        t0 = time.perf_counter()
        while True:
            reqs = traffic.round_requests(ctx.mix, ctx.cfg["vocab_size"],
                                          ctx.seed, rounds)
            with jax.profiler.TraceAnnotation("bench.serve_round"):
                results, sent, dt = sess.round(reqs)
            rounds += 1
            for uid, req in sent.items():
                attempted += 1
                r = results.get(uid)
                if r is None or len(r.tokens) != req.max_new:
                    failed += 1
                    continue
                lat.append(dt)
                tokens += len(r.tokens)
                used_flops += flops.request_flops(ctx.cfg, len(req.prompt),
                                                  len(r.tokens))
                done[uid] = (np.asarray(req.prompt), np.asarray(r.tokens))
            if time.perf_counter() - t0 >= ctx.window_seconds():
                break
        window_s = time.perf_counter() - t0
        counter.active = False
    lat += [window_s] * failed      # a failed request misses every limit
    return {"latencies": lat, "done": done, "attempted": attempted,
            "failed": failed, "tokens": tokens, "flops": used_flops,
            "window_s": window_s}


def run(ctx) -> dict:
    from repro.obs import trace as obs_trace
    sess = Session(ctx.mc, ctx.cfg, ctx.mix, ctx.devs, ctx.seed)
    for reqs in traffic.warmup_requests(ctx.mix, ctx.cfg["vocab_size"],
                                        ctx.seed):
        with jax.profiler.TraceAnnotation("bench.warmup_round"):
            sess.round(reqs)
    if ctx.fault:
        ctx.fault(sess)
    harness.settle()
    setup_s = time.perf_counter() - ctx.t_start

    counter = harness.CompileCounter()
    tracer = harness.Tracer(ctx.trace)
    rec_spans = obs_trace.enable(capacity=1 << 20) if ctx.trace else None
    try:
        w = serve_window(sess, ctx, tracer, counter)
    finally:
        harness.unsettle()
        if ctx.trace:
            obs_trace.disable()
    device = harness.device_info(ctx.devs)
    del sess
    gc.collect()
    jax.clear_caches()            # unload the serve steps first

    readings = {"logit_gap": reference_gap(ctx.cfg, ctx.mix, ctx.seed,
                                           w["done"])}
    occupancy = None
    if rec_spans is not None:
        slots = ctx.mix["serve"]["max_batch"]
        dec = [s.args["active"] / slots for s in rec_spans.spans_of("decode")]
        occupancy = float(np.mean(dec)) if dec else None
    record = {
        "kind": "serve", "window_s": w["window_s"],
        "tokens_per_s": w["tokens"] / w["window_s"], "flops": w["flops"],
        "chips": len(ctx.devs), "peaks": ctx.peaks,
        "window_compiles": counter.count, "decode_occupancy": occupancy,
        "trace": tracer.reduce() if ctx.trace else None,
    }
    return {"record": record, "readings": readings, "device": device,
            "attempted": w["attempted"], "failed": w["failed"],
            "end_to_end": {
                "serve_tokens_per_s": record["tokens_per_s"],
                "serve_latency_p95_ms":
                    harness.percentile(w["latencies"], 95) * 1e3,
                "setup_s": setup_s}}
