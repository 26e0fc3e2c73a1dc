"""Driver `serve_open_loop`: requests enter the program's
`GenerationEngine` by `generate()` on a schedule fixed by the mix and
permuted by the seed, whatever the engine is doing (open loop).

Clients are on this side of `TokenStream`: the sender thread submits
each request when it is due and hands its stream to a reader thread,
which blocks in `stream.get(i)` as a client would and stamps each token
as it returns. Nothing inside the engine is touched; the readers share
the interpreter with the engine's dispatcher thread, and mostly run
while it waits for the device. Time to first token counts from the
moment a request was *due*, so a late sender or a full queue counts
against the system, and how late the sender ran is reported. A request
that fails or is refused counts as the worst: its wait is the whole time
until the run stopped waiting.

The window opens when the first request is due and all requests due in
it are waited for (a minute past the close at most): a late answer is
late, not lost. Gaps and first-token times are of all requests.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List

import numpy as np

#: the traced slice is the window's last seconds; the profiler is
#: stopped (which takes a second or more) once the window has closed
TRACE_SECONDS = 3.0
DRAIN_S = 60.0


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def depth_share(stats0, stats1, max_len: int):
    """Share of the cache's depth that the window's own decode steps
    read: the engine's `decode_depth_share` counts the warm-up too.
    Nothing where the program does not count it."""
    try:
        steps = sum(stats1["decode_steps_by_depth"].values()) \
            - sum(stats0["decode_steps_by_depth"].values())
        read = stats1["decode_read_depth_total"] \
            - stats0["decode_read_depth_total"]
    except KeyError:
        return None
    return read / (steps * max_len) if steps else None


class Client:
    """One request's life on the client's side."""

    __slots__ = ("req", "due", "sent", "stamps", "tokens", "status", "thread")

    def __init__(self, req, due):
        self.req, self.due = req, due
        self.sent = None
        self.stamps: List[float] = []
        self.tokens: List[int] = []
        self.status = "unsent"
        self.thread = None

    def read(self, stream, give_up_at):
        i = 0
        try:
            while True:
                tok = stream.get(i, timeout=max(0.0, give_up_at
                                                - time.perf_counter()))
                if tok is None:
                    break
                self.stamps.append(time.perf_counter())
                self.tokens.append(tok)
                i += 1
            self.status = stream.status or "ok"
        except Exception as e:  # a failed stream raises its failure here
            self.status = f"failed: {type(e).__name__}"


def build_engine(ctx, tracer):
    from bigdl_tpu.serving import GenerationEngine
    e = ctx.mix["engine"]
    return GenerationEngine(
        ctx.adapter.model, slots=e["slots"], max_len=e["max_len"],
        max_new_tokens=ctx.mix["output_len"]["max"],
        prefill_batch=e["prefill_batch"], seq_buckets=e["seq_buckets"],
        queue_capacity=e["queue_capacity"], tracer=tracer)


def run(ctx) -> Dict[str, Any]:
    from benchmarks import trafficgen
    adapter = ctx.adapter
    weights = ctx.reference.init_weights(ctx.cfg, ctx.seed)
    adapter.model.set_params(adapter.served_params(weights))
    adapter.model._state = adapter.model.state_init()
    del weights
    tracer = None
    if ctx.trace:
        from bigdl_tpu.observability.spans import SpanTracer
        tracer = SpanTracer()
    engine = build_engine(ctx, tracer)
    try:
        compiled = engine.warmup()
        schedule = trafficgen.serving_schedule(
            ctx.mix, ctx.cfg["vocab_size"], ctx.seed, ctx.seconds)
        stats0 = engine.generation_stats()
        lowerings0 = ctx.lowerings()
        t0 = time.perf_counter() + 0.05
        give_up_at = t0 + ctx.seconds + DRAIN_S
        clients = [Client(r, t0 + r["due_s"]) for r in schedule]

        def send():
            for c in clients:
                wait = c.due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                if ctx.trace:
                    ctx.trace_from(time.perf_counter() - t0,
                                   ctx.seconds - TRACE_SECONDS)
                c.sent = time.perf_counter()
                try:
                    stream = engine.generate(
                        c.req["prompt"],
                        max_new_tokens=c.req["max_new_tokens"])
                except Exception as e:  # refused at admission
                    c.status = f"refused: {type(e).__name__}"
                    continue
                c.thread = threading.Thread(
                    target=c.read, args=(stream, give_up_at), daemon=True)
                c.thread.start()

        sender = threading.Thread(target=send, daemon=True)
        sender.start()
        sender.join()
        wait = t0 + ctx.seconds - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        ctx.trace_stop()
        for c in clients:
            if c.thread is not None:
                c.thread.join(max(0.0, give_up_at - time.perf_counter()) + 1)
        t_end = time.perf_counter()
        stats1 = engine.generation_stats()
        lowerings = ctx.lowerings() - lowerings0
        compiled_after = engine.compile_count()
    finally:
        engine.close(drain=False)

    failed = [c for c in clients
              if c.status != "ok" or len(c.tokens) != c.req["max_new_tokens"]]
    # the window runs to its close or to the last token, whichever is
    # later (not to `t_end`: a traced run stops the profiler meanwhile)
    t_last = max([t0 + ctx.seconds] + [c.stamps[-1] for c in clients
                                       if c.stamps])
    gaps_ms, ttft_ms, late_ms = [], [], []
    for c in clients:
        late_ms.append(((c.sent or t_end) - c.due) * 1e3)
        if c.stamps:
            ttft_ms.append((c.stamps[0] - c.due) * 1e3)
            gaps_ms.extend(np.diff(c.stamps) * 1e3)
        else:
            ttft_ms.append((t_end - c.due) * 1e3)
    if not gaps_ms:
        raise RuntimeError("no request produced two tokens")

    # free the engine's cache and weights before the reference runs
    served = [{"prompt": c.req["prompt"], "tokens": list(c.tokens),
               "asked": c.req["max_new_tokens"]} for c in clients
              if c.status == "ok"]
    n_tokens = sum(len(c.tokens) for c in clients)
    cache_positions = sum(
        sum(range(len(c.req["prompt"]) + 1,
                  len(c.req["prompt"]) + len(c.tokens)))
        for c in clients if c.tokens)
    prompt_tokens = sum(len(c.req["prompt"]) for c in clients if c.tokens)
    attention_positions = cache_positions + sum(
        len(c.req["prompt"]) * (len(c.req["prompt"]) + 1) // 2
        for c in clients if c.tokens)
    adapter.model.set_params(None)
    del engine

    d = {k: stats1[k] - stats0[k] for k in
         ("decode_steps", "prefill_batches", "prefill_requests",
          "tokens_total")}
    d["decode_s"] = stats1["decode_s_total"] - stats0["decode_s_total"]
    d["prefill_s"] = stats1["prefill_s_total"] - stats0["prefill_s_total"]
    share = depth_share(stats0, stats1, ctx.mix["engine"]["max_len"])
    if share is not None:
        d["decode_depth_share"] = share
    return {
        "t_window": t0, "window_s": t_last - t0,
        "note": f"{len(clients)} requests, {len(failed)} failed, "
                f"{len(gaps_ms)} gaps, sender late by at most "
                f"{max(late_ms):.1f} ms, {lowerings} programs lowered in the "
                f"window",
        "attempted": len(clients), "failed": len(failed),
        # a cell reports those of these that its manifest lists for it
        "end_to_end": {"itl_p50_ms": percentile(gaps_ms, 50),
                       "itl_p99_ms": percentile(gaps_ms, 99)},
        "counters": {**d, "requests": len(clients), "gaps": len(gaps_ms),
                     "tokens_out": n_tokens, "prompt_tokens": prompt_tokens,
                     "cache_positions_read": cache_positions,
                     "attention_positions": attention_positions,
                     "gen_late_ms": late_ms, "ttft_ms": ttft_ms,
                     "itl_ms": [float(g) for g in gaps_ms],
                     "lowerings_in_window": lowerings,
                     "engine_compiles_in_window": compiled_after - compiled},
        "served": served,
    }


def check(ctx, out) -> Dict[str, Any]:
    """Once the window has closed: a sample of the finished requests,
    drawn from the seed, with the longest in it. The reference runs once
    over each prompt with its served tokens; compared is the widest gap
    by which a served token's logit lies below the reference's best."""
    ref = ctx.reference
    served = out["served"]
    numbers = {"answers_short": {"value": float(sum(
        len(s["tokens"]) != s["asked"] for s in served)
        + out["attempted"] - len(served))}}
    if not served:
        numbers["logit_gap_max"] = {"value": float("inf")}
        return numbers
    want = min(int(ctx.mix["check_requests"]), len(served))
    rng = np.random.default_rng(ctx.seed)
    longest = max(range(len(served)),
                  key=lambda i: len(served[i]["prompt"])
                  + len(served[i]["tokens"]))
    picks = [longest] + [int(i) for i in rng.permutation(len(served))
                         if i != longest][:want - 1]
    weights = ref.served_weights(ctx.cfg,
                                 ref.init_weights(ctx.cfg, ctx.seed))
    gap, n = served_gaps(ctx, weights, [served[i] for i in picks])
    numbers["logit_gap_max"] = {"value": gap, "tokens": n,
                                "requests": len(picks)}
    return numbers


def served_gaps(ctx, weights, sample, control: str = ""):
    """Widest gap below the float32 reference's best logit, over every
    served token of `sample` (or, with `control`, over the token that the
    reference computed in that lower precision puts first at the same
    positions), and the number of tokens looked at."""
    import jax
    import jax.numpy as jnp
    ref = ctx.reference
    t_max = ctx.mix["prompt_len"]["max"] + ctx.mix["output_len"]["max"]
    p_max = ctx.mix["output_len"]["max"]
    block = int(ctx.mix.get("check_block", 4))
    fwd = jax.jit(lambda w, t, p, prec: ref.logits_at(ctx.cfg, w, t, p, prec),
                  static_argnums=(3,))
    worst, seen = 0.0, 0
    for i in range(0, len(sample), block):
        rows = sample[i:i + block]
        toks = np.ones((block, t_max), np.int32)
        pos = np.zeros((block, p_max), np.int32)
        picked = np.ones((block, p_max), np.int32)
        valid = np.zeros((block, p_max), bool)
        for j, s in enumerate(rows):
            p, out = len(s["prompt"]), s["tokens"]
            toks[j, :p] = s["prompt"]
            toks[j, p:p + len(out) - 1] = out[:-1]
            pos[j, :len(out)] = np.arange(p - 1, p - 1 + len(out))
            picked[j, :len(out)] = out
            valid[j, :len(out)] = True
        logits = fwd(weights, jnp.asarray(toks), jnp.asarray(pos), "f32")
        if control:
            low = fwd(weights, jnp.asarray(toks), jnp.asarray(pos), control)
            picked = np.asarray(jnp.argmax(low, axis=-1)) + 1
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, jnp.asarray(picked - 1)[..., None],
                                  axis=-1)[..., 0]
        gaps = np.where(valid, np.asarray(best - got), 0.0)
        worst = max(worst, float(gaps.max()))
        seen += int(valid.sum())
    return worst, seen
