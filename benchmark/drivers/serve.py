"""Driver of the serving cells: an open loop over the fleet router's own
public path — ``ServingEngine.start_serve(sched)``, then in one thread admit
every request that is due (``engine.admit``) and ``loop.tick()``. One process
holds the chip; no second process, no server that outlives the run.

Set-up (counted in ``setup_s``): build, ``compile()``, weights on the device
from ``CHECK_SEED``, the engine with its KV pool, a warm wave (its prompts
from ``CHECK_SEED`` too) that compiles the decode step and every prefill
bucket the mix's clipped prompt range can hit (its streams are what the
plain reference is compared with: one value per program and cell, whatever
the run's seed, which draws the window's arrivals, lengths and prompts), and
the pre-roll: arrivals begin ``pre_roll_s`` before the window so that the
window opens at steady occupancy.

Times are taken by the benchmark: a request's clock starts when it was
**due**, which only the generator knows. ``first_token_ms``
is the scheduler's stamp on the clock the benchmark hands it; later tokens
are stamped through the scheduler's public ``on_commit`` hook.

A request due in the window is held to the cell's ``limits``: a first token
within ``ttft_ms`` of its due time, and never more than ``token_gap_ms``
between two of its tokens or since its newest. One that misses either is
``failed``, and a run with a failed request is not ``correct``. So a change
that makes the judged median better by admitting later, by running fewer
slots or by letting a stream stall is refused, without a bound on a tail
that some sixty requests cannot carry.

The traced run (``--trace 1``) takes the profiler's trace of a shorter window
of its own: ``trace_seconds`` of the cell's file or, where the file gives
``trace_steps``, that many decode steps, whichever comes first. Stopping the
profiler and reducing the trace cost in proportion to the device events, and
those follow the steps: bounded by steps, a traced run costs the same
whatever the program's speed.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import spans
from benchmark.drivers.common import (CHECK_SEED, CompileCounter,
                                      mosaic_calls, place_cache,
                                      program_member, start_trace, stop_trace)

# A generated token is accepted where its logit under the plain reference
# (f32, "highest", full forward over prompt + generated tokens) lies within
# this of the reference's largest logit at that position. With random weights
# the arg-max flips on rounding, so tokens are not compared. The system
# computes in bf16 through 48 pre-LN blocks: its logits carry an error of
# about 2^-8 of the logit scale per rounding, accumulated over the depth;
# measured on the chip the worst gap was 0.015 at a logit spread
# (max - median) of 1.04 (PERF.md Findings PR 22; 0.002-0.011 over five more
# seeds of weights and prompts in PR 29, before both came from CHECK_SEED).
# fp8 rounds sixteen times as coarsely as bf16 and would move logits by
# tenths; a dropped bias more.
LOGIT_GAP_TOL = 0.06
# Output tokens of a checked request: enough positions for the comparison,
# few enough that the warm wave stays short (a decode step is ~0.2 s).
CHECK_TOKENS = 16

SNAPSHOT = ("tokens_generated", "prefills", "decode_steps", "kv_bytes_read",
            "host_dispatch_s", "host_device_s", "host_bookkeep_s",
            "host_overlap_s", "host_ticks", "prefill_tokens_computed")


def clock_ms() -> float:
    return time.perf_counter() * 1e3


def snapshot(stats) -> dict:
    return {k: getattr(stats, k) for k in SNAPSHOT}


def buckets_hit(buckets, lo: int, hi: int):
    """The prefill buckets a prompt length in [lo, hi] can land in."""
    out = []
    for i, b in enumerate(buckets):
        below = buckets[i - 1] if i else 0
        if below < hi and b >= lo:
            out.append(b)
    return out


def build_engine(ctx, info):
    import jax

    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.serving import ServingEngine

    eng_cfg = ctx.cell["engine"]
    model_cfg, build = ctx.model_config(batch_size=8)
    config = FFConfig()
    config.parse_args(["-b", "8", "--seed", str(CHECK_SEED)]
                      + list(ctx.config.get("compile_flags", []))
                      + list(ctx.cell.get("compile_flags", [])))
    ff = FFModel(config)
    build(ff, model_cfg)
    with spans.span("compile"):
        # no optimizer: plain SGD, no state beside the weights
        ff.compile(loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    jax.block_until_ready(ff.params)
    kwargs = {k: eng_cfg[k] for k in ("n_slots", "max_decode_len",
                                      "kv_pool_blocks", "max_queue")
              if k in eng_cfg}
    if eng_cfg.get("buckets"):
        kwargs["buckets"] = tuple(eng_cfg["buckets"])
    eng = ServingEngine(ff, **kwargs)
    info["engine"] = {"kv_cache": eng.kv_cache, "serve_loop": eng.serve_loop,
                      "exact_decode": eng.exact_decode,
                      "kv_dtype": eng.kv_dtype, "block": eng.kv_block_size,
                      "pool_blocks": getattr(eng, "kv_pool_blocks", None),
                      "buckets": list(eng.buckets), "n_slots": eng.n_slots}
    return ff, eng


def new_scheduler(eng):
    from flexflow_tpu.serving.scheduler import ContinuousBatchScheduler

    return ContinuousBatchScheduler(
        n_slots=eng.n_slots, max_queue=eng.max_queue, buckets=eng.buckets,
        max_len=eng.max_decode_len, clock=clock_ms)


def make_request(arrival, tag: int):
    from flexflow_tpu.serving.scheduler import Request

    return Request(prompt=arrival.prompt,
                   max_new_tokens=arrival.max_new_tokens, eos_id=None,
                   rng_tag=tag)


def warm_wave(ctx, eng, gen, mix, vocab):
    """Compile what the window will use, and produce the streams the
    reference is compared with: one prompt at the top of every bucket the
    mix can hit, four requests of the mix's own distribution, and each of
    those a second time (equal prompts must give equal streams)."""
    rng = np.random.default_rng([CHECK_SEED, 0xA11])
    lo, hi = int(mix["prompt_len"]["min"]), int(mix["prompt_len"]["max"])
    cap = int(mix["max_total_tokens"])
    reqs = []
    for b in buckets_hit(eng.buckets, lo, hi):
        n = min(b, hi, cap - 4)
        reqs.append(gen.Arrival(len(reqs), 0.0, rng.integers(
            0, vocab, size=n).astype(np.int32), 4))
    sample = gen.generate(mix, 1.0, CHECK_SEED + 7919, 64.0, vocab)[:4]
    for a in sample:
        a.max_new_tokens = min(a.max_new_tokens, CHECK_TOKENS)
    checked = list(range(len(reqs), len(reqs) + 2 * len(sample)))
    # the same prompts again: each is admitted after its twin's prefill, so
    # it takes the prefix-hit (chunk) path (one chunk shape for all: the
    # suffix left to compute is under a block) and is checked like the others
    reqs += sample + sample
    twins = [(i, i + len(sample)) for i in checked[:len(sample)]]
    sched = new_scheduler(eng)
    loop = eng.start_serve(sched)
    live = [make_request(a, i) for i, a in enumerate(reqs)]
    for r in live:
        eng.admit(sched, r)
    while loop.tick():
        pass
    loop.finish()
    return live, checked, twins


def reference_check(ctx, ff, live, checked, twins, info):
    """Fetch what the comparison needs — the plain reference's logits at
    every generated position of the checked requests — and compare."""
    import jax

    ref = ctx.reference().Reference(ff.params, ctx.config)
    # every sequence is padded to the mix's longest, so the reference has one
    # shape and compiles once per checkout, not once per drawn length; under
    # the causal mask the padding cannot reach an earlier position
    pad_to = int(ctx.traffic["max_total_tokens"])
    rows_of, streams = {}, {}
    for i in checked:
        r = live[i]
        ids = np.concatenate([r.prompt, np.asarray(r.generated, np.int32)])
        padded_ids = np.zeros(pad_to, np.int32)
        padded_ids[:len(ids) - 1] = ids[:-1]
        logits = np.asarray(jax.device_get(ref.logits(padded_ids)),
                            np.float32)[:len(ids) - 1]
        rows_of[i] = logits[len(r.prompt) - 1:]     # one per generated token
        streams[i] = list(r.generated)
    stats, verdicts = compare(rows_of, streams, twins)
    info["reference: worst logit gap of a chosen token"] = round(
        stats["logit_gap_max"], 5)
    info["reference: logit spread (max - median), median"] = round(
        stats["logit_spread"], 4)
    info["equal prompts: tokens equal before the streams part, of"] = [
        f"{j}/{len(streams[a])}"
        for j, (a, _) in zip(stats["twin_tokens_equal"], twins)]
    if "twin_tie_gap" in stats:
        info["equal prompts: widest reference gap where streams part"] = \
            round(stats["twin_tie_gap"], 5)
    verdicts["warm_wave_all_ok"] = all(
        r.outcome == "ok" and len(r.generated) == r.max_new_tokens
        for r in live)
    return stats, verdicts


def compare(rows_of, streams, twins):
    """The comparison itself, arrays in: (the statistics, the verdicts).
    ``rows_of[i]`` holds the reference's logits at every generated position
    of checked request ``i`` (tokens, vocabulary), ``streams[i]`` its tokens,
    ``twins`` the pairs of requests with equal prompts. ``logit_gap_max`` is
    what the guard metric ``check_logit_gap_max`` reports."""
    worst_gap, spread = 0.0, []
    for i, rows in rows_of.items():
        tokens = np.asarray(streams[i])
        chosen = rows[np.arange(len(tokens)), tokens]
        worst_gap = max(worst_gap, float(np.max(rows.max(axis=1) - chosen)))
        spread.append(float(np.median(rows.max(axis=1)
                                      - np.median(rows, axis=1))))
    # Two equal prompts: one takes the bucket path, its twin the prefix-hit
    # path, and in bf16 the two may round a near-tie differently (PERF.md,
    # correct). So a pair's streams are equal up to the first position where
    # the reference itself all but ties the two tokens chosen; after it the
    # two continue other texts, each held to the reference by the gap above.
    equal_before, ties, same = [], [], True
    for a, b in twins:
        ga, gb = streams[a], streams[b]
        j = next((k for k in range(min(len(ga), len(gb))) if ga[k] != gb[k]),
                 min(len(ga), len(gb)))
        equal_before.append(j)
        same = same and len(ga) == len(gb)
        if j < min(len(ga), len(gb)):
            ties.append(abs(float(rows_of[a][j][ga[j]]
                                  - rows_of[a][j][gb[j]])))
    stats = {"logit_gap_max": worst_gap,
             "logit_spread": float(np.median(spread)),
             "twin_tokens_equal": equal_before}
    if ties:
        stats["twin_tie_gap"] = max(ties)
        same = same and max(ties) <= LOGIT_GAP_TOL
    return stats, {
        "tokens_within_reference_gap": worst_gap <= LOGIT_GAP_TOL,
        "equal_prompts_equal_streams_up_to_a_tie": same}


def compared(stats) -> list:
    """(name, value, limit) of every number the comparison holds to a
    limit, for the run's last lines."""
    out = [("check_logit_gap_max", stats["logit_gap_max"], LOGIT_GAP_TOL)]
    if "twin_tie_gap" in stats:
        out.append(("twin_tie_gap", stats["twin_tie_gap"], LOGIT_GAP_TOL))
    return out


def decode_step_text(eng) -> str:
    """The compiled decode step's text. No public entry point gives it: the
    step is lowered again through the engine's own step builder (a hit in
    the compile cache)."""
    import jax.numpy as jnp

    what_for = "the compiled decode step's text"
    fn = program_member(eng, "_decode_fn", what_for)(
        guard=program_member(eng, "_last_guard", what_for))
    tokens = jnp.zeros((eng.n_slots, 1), jnp.int32)
    return fn.lower(eng.model.params, [tokens], eng.state).compile().as_text()


def percentile(values, q: float) -> float:
    """Percentile over a list that may hold +inf (a failed request)."""
    v = np.sort(np.asarray(values, np.float64))
    if len(v) == 0:
        return float("inf")
    return float(v[min(len(v) - 1, int(np.ceil(q / 100.0 * len(v))) - 1)])


def latencies(rows, limits, judged_until_ms):
    """(ttft list, tpot list, failed count) of the requests due in a window.
    A row is (request, due time, refused at the door, its newest token's
    time, the widest gap between two of its tokens).

    A request's clock starts when it was **due**, not when the driver loop
    got round to submitting it. Failed is one that was refused, left with
    another outcome than ``ok``, had no first token within
    ``limits["ttft_ms"]`` of its due time, waited longer than
    ``limits["token_gap_ms"]`` between two tokens, or is unfinished with its
    newest token older than that. ``judged_until_ms`` is when the judging
    stops: the end of the run, or in a traced run the window's close,
    because stopping the profiler stalls the loop for seconds and what a
    request waits after that is the profiler's doing. One with no first
    token enters the TTFT list as +inf. A request still decoding when the
    run ends is not failed (at 0.25 s a token a 256-token answer outlasts
    any grace a run can afford): its token gaps count up to its newest
    token."""
    ttft, tpot, failed = [], [], 0
    for r, due, refused, last, widest_gap in rows:
        started = r is not None and not refused and bool(r.first_token_ms)
        ok = started and r.outcome in (None, "ok")
        first = r.first_token_ms if started else float("inf")
        late = min(first, judged_until_ms) - due > limits["ttft_ms"]
        stalled = ok and (
            (widest_gap or 0.0) > limits["token_gap_ms"]
            or (r.outcome is None and judged_until_ms - (last or first)
                > limits["token_gap_ms"]))
        if not ok or late or stalled:
            failed += 1
        ttft.append(first - due)
        if ok and len(r.generated) > 1 and last:
            tpot.append((last - r.first_token_ms) / (len(r.generated) - 1))
    return ttft, tpot, failed


def finite_or_cap(x: float) -> float:
    return x if np.isfinite(x) else 1e9


def run(ctx) -> dict:
    from flexflow_tpu.serving.scheduler import ServingRejection

    place_cache()
    counter = CompileCounter()
    cell, mix = ctx.cell, ctx.traffic
    info, checks = {}, {}
    walls = ctx.walls
    t = time.perf_counter()
    ff, eng = build_engine(ctx, info)
    build_s = time.perf_counter() - t
    walls.add("build_compile_init", build_s)
    vocab = int(ctx.config["vocab_size"])
    gen = ctx.generator()

    t = time.perf_counter()
    live, checked, twins = warm_wave(ctx, eng, gen, mix, vocab)
    warm_s = time.perf_counter() - t
    walls.add("warm_wave", warm_s)
    checks["decode_compiles_once"] = eng.decode_compiles == 1
    t = time.perf_counter()
    check_stats, verdicts = reference_check(ctx, ff, live, checked, twins,
                                            info)
    checks.update(verdicts)
    check_s = time.perf_counter() - t
    walls.add("reference_check", check_s)
    del live

    # the compiled decode step's text: the kernel that should run is in it
    # (every run), and its op_name metadata gives the traced run's breakdown
    # its node scopes
    t = time.perf_counter()
    text = decode_step_text(eng)
    info["mosaic_kernels"] = sorted(mosaic_calls(text))
    if ctx.devices[0].platform == "tpu":
        checks["flash_decode_in_step"] = \
            "flash_decode" in info["mosaic_kernels"]
    scopes = None
    rt = None
    if ctx.trace:
        from benchmark.reduce import xplane
        from flexflow_tpu.obs import enable_reqtrace

        scopes = xplane.scope_map(text)
        rt = enable_reqtrace()
    del text
    text_s = time.perf_counter() - t
    walls.add("step_text", text_s)
    seconds = min(ctx.seconds, float(cell.get("trace_seconds", 5.0))) \
        if ctx.trace else ctx.seconds
    trace_steps = int(cell.get("trace_steps", 0)) if ctx.trace else 0
    pre_roll = float(mix["pre_roll_s"])
    # after the window: until every request due in it has its first token
    # (at most drain_grace_s); the traced run waits for them to finish, so
    # that their RequestRecords exist
    grace = float(mix["traced_drain_s"] if ctx.trace
                  else mix["drain_grace_s"])
    rate = float(cell["rate_rps"])
    limits = {k: float(v) for k, v in cell["limits"].items()}
    arrivals = gen.generate(mix, rate, ctx.seed, pre_roll + seconds, vocab,
                            initial_inflight=int(cell.get(
                                "pre_roll_inflight", 0)))
    sched = new_scheduler(eng)
    token_times = {}   # per request: every commit's time (traced run)
    last_ms = {}       # per request: its newest commit's time
    widest_gap = {}    # per request: the longest wait between two commits

    def on_commit(req):
        now = clock_ms()
        k = req.rng_tag
        # a traced run judges waits up to the window's close (see latencies)
        if k in last_ms and (not ctx.trace or now <= w_close):
            widest_gap[k] = max(widest_gap.get(k, 0.0), now - last_ms[k])
        last_ms[k] = now
        if ctx.trace:
            token_times.setdefault(k, []).append(now)

    sched.on_commit = on_commit
    loop = eng.start_serve(sched)
    stats = loop.stats
    n = len(arrivals)
    reqs = [None] * n
    admitted_ms = [None] * n
    rejected = set()
    i = 0
    snap_open = snap_close = None
    trace_file = ""
    t0 = clock_ms()
    due_ms = np.array([t0 + a.due_s * 1e3 for a in arrivals])
    w_open, w_close = t0 + pre_roll * 1e3, t0 + (pre_roll + seconds) * 1e3
    in_window = [k for k in range(n) if w_open <= due_ms[k] < w_close]
    setup_s = ctx.since_start() + pre_roll
    window_cm = None

    def settled() -> bool:
        want_done = ctx.trace
        return all(reqs[k] is not None and (
            k in rejected or reqs[k].outcome is not None
            or (not want_done and reqs[k].first_token_ms))
            for k in in_window)

    while True:
        now = clock_ms()
        if snap_open is None and now >= w_open:
            if ctx.trace:
                with walls.phase("start_trace"):
                    start_trace(ctx)
                window_cm = spans.span(spans.WINDOW)
                window_cm.__enter__()
                now = clock_ms()
            snap_open = (now, snapshot(stats), sched.active + sched.queued,
                         counter.n, len(counter.names))
        if (trace_steps and snap_open is not None and now < w_close
                and stats.decode_steps - snap_open[1]["decode_steps"]
                >= trace_steps):
            # closed by its steps: the window ends here, and what was due
            # after it is not offered (as nothing arrives after a window that
            # closes by the clock)
            w_close = now
            n = max(i, int(np.searchsorted(due_ms, w_close, side="left")))
            in_window = [k for k in range(n) if w_open <= due_ms[k]]
        if snap_close is None and now >= w_close:
            snap_close = (now, snapshot(stats), sched.active + sched.queued,
                          counter.n, sched.queued, len(counter.names))
            walls.add("pre_roll", (snap_open[0] - t0) / 1e3)
            walls.add("window", (now - snap_open[0]) / 1e3)
            if window_cm is not None:
                window_cm.__exit__(None, None, None)
                with walls.phase("stop_trace"):
                    trace_file = stop_trace(ctx)
            drain_from = clock_ms()
        if i < n and due_ms[i] <= now:
            with spans.span("admit"):
                while i < n and due_ms[i] <= now:
                    reqs[i] = make_request(arrivals[i], i)
                    admitted_ms[i] = now
                    try:
                        eng.admit(sched, reqs[i])
                    except ServingRejection:
                        rejected.add(i)
                    i += 1
        if snap_close is not None and (
                now > w_close + grace * 1e3 or (i >= n and settled())):
            break
        with spans.span("tick"):
            progressed = loop.tick()
        if not progressed:
            # nothing to do until the next request is due; wake at least
            # every 5 ms so that the window's edges are seen
            nxt = due_ms[i] if i < n else now + 5.0
            with spans.span("generator_sleep"):
                time.sleep(min(max(nxt - clock_ms(), 0.2), 5.0) / 1e3)
    end_ms = clock_ms()
    walls.add("drain", (end_ms - drain_from) / 1e3)
    still_running = sched.active + sched.queued
    with walls.phase("loop_finish"):
        loop.finish()

    # ---- reduce: requests due inside the window
    ttft, tpot, failed = latencies(
        [(reqs[k], due_ms[k], k in rejected, last_ms.get(k),
          widest_gap.get(k)) for k in in_window],
        limits, snap_close[0] if ctx.trace else end_ms)
    delta = {k: snap_close[1][k] - snap_open[1][k] for k in SNAPSHOT}
    measured_s = (snap_close[0] - snap_open[0]) / 1e3
    compiles_in_window = snap_close[3] - snap_open[3]
    lag = [admitted_ms[k] - due_ms[k] for k in in_window]
    checks["no_compile_in_window"] = compiles_in_window == 0
    checks["nothing_failed"] = failed == 0
    checks["decode_compiles_once_after"] = eng.decode_compiles == 1
    info.update({
        "requests_due_in_window": len(in_window), "rate_rps": rate,
        "still_running_at_end": still_running, "rejected": len(rejected),
        "backlog (active + queued) at window open/close": (snap_open[2],
                                                           snap_close[2]),
        "queued_at_close": snap_close[4],
        "window_tokens": delta["tokens_generated"],
        "window_decode_steps": delta["decode_steps"],
        "window_prefills": delta["prefills"],
        "measured_window_s": round(measured_s, 4),
        "queue_depth_hwm": sched.queue_depth_hwm,
        "ttft_p50_ms": round(percentile(ttft, 50), 3),
        "ttft_p90_ms": round(finite_or_cap(percentile(ttft, 90)), 3),
        "ttft_p99_ms": round(finite_or_cap(percentile(ttft, 99)), 3),
        "tpot_requests": len(tpot), "limits": limits,
        "widest_token_gap_ms": round(max(
            [widest_gap.get(k, 0.0) for k in in_window] or [0.0]), 3),
        "setup_split_s": {"build_compile_init": round(build_s, 2),
                          "warm_wave": round(warm_s, 2),
                          "reference_check": round(check_s, 2),
                          "step_text": round(text_s, 2),
                          "pre_roll": pre_roll},
        "after_window_s": round((clock_ms() - w_close) / 1e3, 2),
        "compiles_in_window": compiles_in_window,
        "prefix_hits": stats.prefix_hits,
        "outcomes": dict(stats.outcomes)})
    if compiles_in_window:
        info["lowered_in_window"] = counter.names[snap_open[4]:snap_close[5]]
    records = None
    if rt is not None:
        with walls.phase("request_records"):
            records = rt.records()
    facts = {
        "kind": "serve", "info": info, "checks": checks,
        "check_stats": check_stats, "compared": compared(check_stats),
        "correct": all(checks.values()),
        "attempted": len(in_window), "failed": failed,
        "end_to_end": {
            "tpot_p50_ms": (float(np.median(tpot)) if tpot else 1e9, "ms"),
            "setup_s": (setup_s, "s")},
        "ttft_p90_ms": finite_or_cap(percentile(ttft, 90)),
        "delta": delta, "measured_s": measured_s, "n_slots": eng.n_slots,
        "steps": delta["decode_steps"], "peaks": ctx.peaks,
        "compile_s": build_s + warm_s, "step_module": "jit_decode",
        "generator_lag_ms": lag, "window_ms": (w_open, w_close),
        "due_ms": {k: float(due_ms[k]) for k in in_window},
        "submit_ms": {k: admitted_ms[k] for k in in_window},
        "token_times_ms": token_times, "trace_file": trace_file,
        "request_records": records,
        "rids": {reqs[k].rid: k for k in in_window},
        "scopes": scopes,
    }
    return facts
