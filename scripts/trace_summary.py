#!/usr/bin/env python
"""Summarize a flexflow_tpu obs artifact: top-N phase time table.

Consumes any of the subsystem's outputs and prints where the time (or the
search's attention) went, so BENCH rounds can diff phase breakdowns between
PRs without loading Perfetto:

* Chrome trace-event JSON (``--trace-file`` / ``Tracer.write``): aggregates
  complete ('X') spans by name — count, total/mean/max wall.
* telemetry JSON (``--telemetry-file`` / ``StepTelemetry.write``): step
  count, compile-vs-steady split, samples/sec, MFU, memory.
* search JSONL (``--search-log`` / ``SearchLog``, also the tracer's JSONL
  event sink): iterations, accept rate, best-so-far cost trajectory.

Usage: python scripts/trace_summary.py FILE [-n TOP]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# standalone invocation (python scripts/trace_summary.py ...): the repo
# root is not on sys.path, and the searched-plan line imports the
# schedule/pod describe helpers from the package
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def load(path: str):
    """Returns ("trace"|"telemetry"|"jsonl", payload)."""
    with open(path) as f:
        head = f.read(1)
        f.seek(0)
        if head == "{":
            try:
                data = json.load(f)
            except json.JSONDecodeError:
                f.seek(0)
                return "jsonl", _load_jsonl(f)
            if "traceEvents" in data:
                return "trace", data
            if "steps" in data or "loss_history" in data \
                    or "phase" in data:
                return "telemetry", data
            # a single-line JSONL file (one-iteration search log, tail
            # fragment) also parses as one JSON object — route by shape
            return "jsonl", [data]
        return "jsonl", _load_jsonl(f)


def _load_jsonl(f):
    records = []
    for line in f:
        line = line.strip()
        if not line:
            continue
        try:
            records.append(json.loads(line))
        except json.JSONDecodeError:
            continue
    return records


def _fmt_ms(us: float) -> str:
    return f"{us / 1e3:10.3f}"


def summarize_trace(data, top: int) -> None:
    spans = {}
    counters = {}
    n_instant = 0
    for ev in data.get("traceEvents", []):
        ph = ev.get("ph")
        if ph == "X":
            s = spans.setdefault(ev["name"], [0, 0.0, 0.0])
            s[0] += 1
            s[1] += ev.get("dur", 0.0)
            s[2] = max(s[2], ev.get("dur", 0.0))
        elif ph == "C":
            counters[ev["name"]] = ev.get("args", {})
        elif ph == "i":
            n_instant += 1
    rows = sorted(spans.items(), key=lambda kv: -kv[1][1])[:top]
    print(f"{'phase':24s} {'count':>6s} {'total_ms':>10s} "
          f"{'mean_ms':>10s} {'max_ms':>10s}")
    for name, (cnt, tot, mx) in rows:
        print(f"{name:24s} {cnt:6d} {_fmt_ms(tot)} "
              f"{_fmt_ms(tot / cnt)} {_fmt_ms(mx)}")
    if counters:
        print("\ncounters (last value):")
        for name, args in counters.items():
            print(f"  {name} = {args.get(name, args)}")
    # serving digest (ISSUE 6): the prefill/decode spans the ServingEngine
    # emits, folded into one line — tokens/sec-shaped, not span-table-shaped
    if "decode_step" in spans or "prefill" in spans:
        d = spans.get("decode_step", [0, 0.0, 0.0])
        p = spans.get("prefill", [0, 0.0, 0.0])
        line = f"\nserving digest: {d[0]} decode steps"
        if d[0]:
            line += f" (mean {d[1] / d[0] / 1e3:.3f} ms)"
        line += f", {p[0]} prefills"
        if p[0]:
            line += f" (mean {p[1] / p[0] / 1e3:.3f} ms)"
        print(line)
    if n_instant:
        print(f"\n{n_instant} instant events (not aggregated)")


def _block(data, key, render) -> None:
    """Render one telemetry block defensively: telemetry files and this
    summary evolve in different PRs, so an older (or newer) file may hold
    a block shaped differently than this renderer expects. A malformed
    block degrades to a one-line notice instead of a traceback — the rest
    of the summary still prints and the exit stays 0."""
    blk = data.get(key)
    if not blk:
        return
    try:
        render(blk)
    except (TypeError, KeyError, ValueError, IndexError, AttributeError):
        print(f"note: telemetry block {key!r} does not match this "
              "summary's schema (file from another PR?) — skipped")


def summarize_telemetry(data, top: int) -> None:
    if "epochs" in data:  # keras TelemetryCallback: one summary per epoch
        eps = data["epochs"]
        print(f"telemetry with {len(eps)} epoch records; last epoch:")
        if eps:
            summarize_telemetry(eps[-1], top)
        return
    print(f"phase: {data.get('phase')}  steps: {data.get('steps')}  "
          f"batch_size: {data.get('batch_size')}")

    def _steps(first):
        line = f"first step: {first * 1e3:.1f} ms"
        if "steady_step_s" in data:
            line += f"   steady step: {data['steady_step_s'] * 1e3:.3f} ms"
        print(line)

    _block(data, "first_step_s", _steps)
    if "programs_built" in data:
        # the run's builds by the registry (obs/builds.py)
        names = ", ".join(f"{n} x{k}" for n, k in sorted(
            data.get("by_name", {}).items()))
        print(f"programs built: {data['programs_built']} in "
              f"{data.get('build_s', 0.0) * 1e3:.1f} ms"
              + (f" ({names})" if names else ""))
    if "samples_per_sec" in data:
        print(f"throughput: {data['samples_per_sec']} samples/s")
    if "estimated_mfu" in data:
        print(f"estimated MFU: {data['estimated_mfu']}")

    def _mem(mem):
        peak = mem.get("peak_memory_in_bytes")
        if peak:
            print(f"XLA peak memory: {peak / 2 ** 20:.1f} MiB")

    _block(data, "device_memory", _mem)

    def _res(res):
        # fault-tolerance headline (ISSUE 4): how eventful the run was and
        # where it last picked itself back up
        line = (f"faults: {res.get('fault_events', 0)} "
                f"({res.get('skipped_steps', 0)} steps skipped)   "
                f"recoveries: {res.get('recovery_events', 0)}   "
                f"checkpoints: {res.get('checkpoints_saved', 0)}")
        if res.get("last_resume_step") is not None:
            line += f"   last resume at step {res['last_resume_step']}"
        print(line)

    _block(data, "resilience", _res)

    def _ss(ss):
        # strategy-safety headline (ISSUE 5): did the plan survive its
        # verification, and which strategy did the run actually train under
        line = (f"strategy fallbacks: {ss.get('fallbacks', 0)}   "
                f"audits: {ss.get('audit_runs', 0)} "
                f"({ss.get('audit_failures', 0)} failed)")
        if ss.get("final_strategy"):
            line += f"   final strategy: {ss['final_strategy']}"
        print(line)

    _block(data, "strategy_safety", _ss)

    def _st(st):
        # ShardLint headline (ISSUE 7): static analyses run and what
        # they rejected before any compile was paid
        line = (f"static analysis: {st.get('checks', 0)} checks, "
                f"{st.get('rejects', 0)} rejected")
        if st.get("rules"):
            line += f"   rules fired: {', '.join(st['rules'])}"
        print(line)

    _block(data, "strategy_static", _st)

    def _cal(cal):
        # calibration digest (ISSUE 8): how straight the simulator's ruler
        # is, which op bent it furthest, and whether the closed loop
        # repaired it during this run
        line = (f"calibration: {cal.get('profiled_keys', 0)} keys profiled"
                f", aggregate sim-vs-measured "
                f"{cal.get('aggregate_ratio', '?')}")
        if cal.get("worst_key") is not None:
            line += (f"   worst: {cal['worst_key']} "
                     f"({cal.get('worst_ratio', '?')})")
        line += (f"   out of band: {cal.get('out_of_band', 0)} "
                 f"(tol {cal.get('tolerance', '?')})")
        print(line)
        if cal.get("recalibrations"):
            after = cal.get("ratio_after")
            print(f"  recalibrations applied: {cal['recalibrations']} "
                  f"({cal.get('invalidated_entries', 0)} delta-cost "
                  f"entries invalidated)"
                  + (f"   aggregate ratio after repair: {after}"
                     if after is not None else ""))

    _block(data, "calibration", _cal)

    def _srv(srv):
        # serving headline (ISSUE 6): request/token volume, queue pressure
        # and the per-token latency tail of the serve run
        line = (f"serving: {srv.get('requests_served', 0)} requests, "
                f"{srv.get('tokens_generated', 0)} tokens   "
                f"queue hwm: {srv.get('queue_depth_hwm', 0)}")
        if srv.get("tokens_per_s") is not None:
            line += f"   {srv['tokens_per_s']} tokens/s"
        if srv.get("p99_token_ms") is not None:
            line += (f"   p50/p99: {srv.get('p50_token_ms')}/"
                     f"{srv['p99_token_ms']} ms")
        print(line)
        # sequence-parallel decode (ISSUE 18): the per-shard-chip KV
        # residency at measured fill — the recorded side of the "KV
        # exceeds one chip" criterion
        if srv.get("kv_hbm_per_chip_bytes") is not None:
            b = srv["kv_hbm_per_chip_bytes"]
            size = (f"{b / 2 ** 20:.1f} MiB" if b >= 2 ** 20
                    else f"{b / 2 ** 10:.1f} KiB")
            print(f"  kv per shard chip: {size} at measured fill")
        if srv.get("decode_grid_live_share") is not None:
            print(f"  decode attention kernel: {srv['kv_tiles_live']} of "
                  f"{srv['kv_tiles_grid']} steps fold a key tile "
                  f"({100 * srv['decode_grid_live_share']:.1f}%)")
        if srv.get("moe_pairs_here"):
            print(f"  routed layers at decode: {srv['moe_pairs_here']} "
                  f"(row, expert) pairs held here over "
                  f"{srv['moe_experts_live']} expert reads; fullest expert "
                  f"{srv['moe_load_max_permille'] / 1000:.2f} x its "
                  f"layer's mean; {srv.get('moe_bounded_steps', 0)} of "
                  f"{srv.get('moe_layer_steps', 0)} layer-steps on the "
                  f"bounded path")
        if srv.get("recurrent_state_bytes"):
            print(f"  recurrent state at decode: "
                  f"{srv['recurrent_state_bytes'] / 1e9:.2f} GB read plus "
                  f"written, {srv['recurrent_slots_live']} live slot-steps; "
                  f"{srv.get('recurrent_state_bytes_at_rest', 0) / 1e9:.2f} "
                  f"GB at rest on the chip, "
                  f"{srv.get('state_heads_a_row', 1)} head(s) a row")
        if srv.get("prefill_rows"):
            print(f"  one-shot prefills: {srv['prefill_rows']} rows "
                  f"computed, {srv['prefill_rows_real']} of them real "
                  f"({100 * srv['prefill_rows_real'] / srv['prefill_rows']:.1f}%)")

    _block(data, "serving", _srv)

    def _prefix(pf):
        # prefix-cache headline (ISSUE 14): how much prefill the radix
        # trie saved and how much chunked scheduling ran
        rate = pf.get("reuse_rate", 0.0)
        line = (f"prefix cache: reuse {round(100 * rate, 1)}% "
                f"({pf.get('tokens_reused', 0)} tokens reused / "
                f"{pf.get('tokens_computed', 0)} computed), "
                f"{pf.get('hits', 0)} hits, "
                f"chunked prefills {pf.get('chunked_prefills', 0)}")
        if pf.get("evictions"):
            line += f", evictions {pf['evictions']}"
        print(line)

    _block(data, "serving_prefix", _prefix)

    def _srvres(sr):
        # serving-under-failure headline (ISSUE 9): the outcome ledger of
        # the serve run — every request under exactly one outcome — and
        # how hard the resilience layer had to work
        oc = sr.get("outcomes", {})
        parts = [f"{k}={oc[k]}" for k in
                 ("ok", "deadline_exceeded", "shed", "quota_exceeded",
                  "decode_fault", "preempted") if oc.get(k)]
        line = "serving resilience: " + (" ".join(parts) or "no outcomes")
        if sr.get("shed_rate"):
            line += f"   shed rate {sr['shed_rate']}"
        if sr.get("deadline_miss_rate"):
            line += f"   deadline misses {sr['deadline_miss_rate']}"
        print(line)
        if sr.get("quarantines") or sr.get("drains") or sr.get("replans"):
            print(f"  quarantines: {sr.get('quarantines', 0)}   "
                  f"drains: {sr.get('drains', 0)}   "
                  f"replans: {sr.get('replans', 0)}")

    _block(data, "serving_resilience", _srvres)

    def _fleet(fl):
        # fleet headline (ISSUE 11): the multi-replica router's ledger,
        # how traffic split across fault domains, and how hard the
        # failover/hedging/health machinery worked
        oc = fl.get("outcomes", {})
        parts = [f"{k}={oc[k]}" for k in
                 ("ok", "deadline_exceeded", "shed", "quota_exceeded",
                  "decode_fault", "preempted") if oc.get(k)]
        print(f"fleet: {fl.get('replicas', 0)} replicas, "
              f"{fl.get('requests', 0)} requests, "
              f"{fl.get('tokens_generated', 0)} tokens over "
              f"{fl.get('ticks', 0)} ticks   "
              + (" ".join(parts) or "no outcomes"))
        line = f"  dispatches: {fl.get('dispatches', [])}"
        if fl.get("shed_rate"):
            line += f"   shed rate {fl['shed_rate']}"
        if fl.get("affinity_hits"):
            line += f"   affinity hits {fl['affinity_hits']}"
        print(line)
        if (fl.get("failovers") or fl.get("migrations")
                or fl.get("hedges") or fl.get("circuit_opens")):
            print(f"  failovers: {fl.get('failovers', 0)}   "
                  f"migrations: {fl.get('migrations', 0)}   "
                  f"hedges: {fl.get('hedges', 0)} "
                  f"(twin wins {fl.get('hedge_twin_wins', 0)})   "
                  f"circuit opens: {fl.get('circuit_opens', 0)}   "
                  f"probes: {fl.get('probes', 0)}")
        # multi-tenant rows (ISSUE 19): absent on pre-tenant files —
        # this block simply doesn't print then
        for t, row in sorted((fl.get("tenants") or {}).items()):
            toc = row.get("outcomes", {})
            tparts = " ".join(f"{k}={v}" for k, v in sorted(toc.items()))
            print(f"  tenant {t}: {row.get('requests', 0)} requests, "
                  f"{row.get('tokens', 0)} tokens   "
                  + (tparts or "no outcomes"))
        asc = fl.get("autoscale")
        if asc:
            print(f"  autoscale: {asc.get('ups', 0)} up / "
                  f"{asc.get('downs', 0)} down"
                  + (f"   quota sheds: {fl['quota_sheds']}"
                     if fl.get("quota_sheds") else ""))
        elif fl.get("quota_sheds"):
            print(f"  quota sheds: {fl['quota_sheds']}")

    _block(data, "fleet", _fleet)

    def _journal(j):
        # crash-durability headline (ISSUE 20): how much the write-ahead
        # request journal worked, whether this run was a recovery, and
        # how quickly the backlog got back through the door. Pre-journal
        # telemetry files carry no "serving_journal" block, so this
        # simply doesn't print on them.
        line = (f"request journal: {j.get('appended', 0)} records, "
                f"{j.get('syncs', 0)} group commits")
        if j.get("dedupe_hits"):
            line += f"   dedupe hits {j['dedupe_hits']}"
        if j.get("compacted_segments"):
            line += f"   compacted {j['compacted_segments']} segment(s)"
        print(line)
        if j.get("replayed") or j.get("truncated_records"):
            print(f"  recovery: {j.get('replayed', 0)} rids replayed in "
                  f"{j.get('recovery_wall_s', 0)} s   torn-tail records "
                  f"truncated: {j.get('truncated_records', 0)}")

    _block(data, "serving_journal", _journal)

    def _loss(losses):
        show = losses[:top]
        print(f"loss: first {len(show)} of {len(losses)}: "
              + ", ".join(f"{v:.4f}" for v in show)
              + (f" ... final {losses[-1]:.4f}" if len(losses) > top else ""))

    _block(data, "loss_history", _loss)


def _pctl(vals, q):
    if not vals:
        return 0.0
    s = sorted(vals)
    return s[min(int(q * (len(s) - 1) + 0.5), len(s) - 1)]


def _request_digest(reqs) -> None:
    """Per-request latency decomposition (ISSUE 16): p50/p99 of the
    queue/prefill/decode/stall phase split per outcome class, replica
    hop counts, prefix reuse and hedge volume — the RequestRecord JSONL
    stream (obs/reqtrace.py, docs/observability.md) in ten lines."""
    vs = {r.get("v") for r in reqs}
    if vs - {1}:
        # newer/older schema: show what we can, say what we skipped
        print(f"note: request records carry schema version(s) "
              f"{sorted(v for v in vs if v != 1)}; fields this summary "
              "does not know are ignored")
    by_outcome = {}
    for r in reqs:
        by_outcome.setdefault(r.get("outcome") or "?", []).append(r)
    print(f"request trace: {len(reqs)} requests")
    print(f"  {'outcome':18s} {'n':>5s}"
          + "".join(f" {p + '_p50':>11s} {p + '_p99':>11s}"
                    for p in ("queue", "prefill", "decode", "stall")))
    for outcome, rs in sorted(by_outcome.items(),
                              key=lambda kv: -len(kv[1])):
        row = f"  {outcome:18s} {len(rs):5d}"
        for p in ("queue", "prefill", "decode", "stall"):
            vals = [float(r.get(p + "_ms") or 0.0) for r in rs]
            row += f" {_pctl(vals, .5):11.2f} {_pctl(vals, .99):11.2f}"
        print(row)
    hops = sum(len(r.get("hops") or ()) for r in reqs)
    multi = sum(1 for r in reqs if len(r.get("replicas") or ()) > 1)
    hedged = sum(1 for r in reqs if r.get("hedged"))
    reused = sum(int(r.get("prefix_hit_tokens") or 0) for r in reqs)
    print(f"  hops: {hops} ({multi} requests touched >1 replica)   "
          f"hedged: {hedged}   prefix tokens reused: {reused}")
    ttfts = [float(r["first_token_ms"]) - float(r["arrival_ms"])
             for r in reqs
             if r.get("first_token_ms") and r.get("arrival_ms") is not None]
    if ttfts:
        print(f"  TTFT p50/p99: {_pctl(ttfts, .5):.2f}/"
              f"{_pctl(ttfts, .99):.2f} ms")
    # per-tenant digest (ISSUE 19): per-tier TTFT tail + outcome split.
    # Pre-tenant trace files carry no "tenant" key (or null) — the block
    # degrades to nothing, by design
    by_tenant = {}
    for r in reqs:
        t = r.get("tenant")
        if t:
            by_tenant.setdefault(t, []).append(r)
    if by_tenant:
        print("  per-tenant:")
        for t, rs in sorted(by_tenant.items()):
            tt = [float(r["first_token_ms"]) - float(r["arrival_ms"])
                  for r in rs if r.get("first_token_ms")
                  and r.get("arrival_ms") is not None]
            ocs = {}
            for r in rs:
                k = r.get("outcome") or "?"
                ocs[k] = ocs.get(k, 0) + 1
            line = (f"    {t:12s} {len(rs):5d} req   TTFT p50/p99: "
                    + (f"{_pctl(tt, .5):.2f}/{_pctl(tt, .99):.2f} ms"
                       if tt else "-/-"))
            line += "   " + " ".join(f"{k}={v}"
                                     for k, v in sorted(ocs.items()))
            print(line)
    dropped = sum(int(r.get("dropped_notes") or 0) for r in reqs)
    if dropped:
        print(f"  WARNING: {dropped} trace notes dropped "
              "(per-request cap hit — timelines above are truncated)")


def summarize_jsonl(records, top: int) -> None:
    # RequestRecord streams (obs/reqtrace.py) route to their own digest;
    # mixed sinks fall through to the generic aggregation for the rest
    reqs = [r for r in records if r.get("kind") == "request"]
    if reqs:
        try:
            _request_digest(reqs)
        except (TypeError, KeyError, ValueError, IndexError,
                AttributeError):
            print("note: request records do not match this summary's "
                  "schema (file from another PR?) — skipped")
        records = [r for r in records if r.get("kind") != "request"]
        if not records:
            return
        print()
    # search logs carry cost_ms; generic event sinks aggregate by name.
    # "result"/"sweep_result" records are summaries, not iterations — keep
    # them out of the iteration count / accept rate / trajectory
    iters = [r for r in records
             if "cost_ms" in r
             and r.get("event") not in ("result", "sweep_result")]
    if iters:
        kinds = {r.get("search", r.get("event", "?")) for r in iters}
        accepted = sum(1 for r in iters if r.get("accepted"))
        best = min(r["cost_ms"] for r in iters)
        print(f"search log ({'/'.join(sorted(kinds))}): "
              f"{len(iters)} iterations, {accepted} accepted "
              f"({accepted / len(iters) * 100:.1f}%)")
        print(f"best candidate cost: {best:.4f} ms")
        final = [r for r in records if r.get("event") == "result"]
        if final:
            print(f"result: {json.dumps(final[-1])}")
            r = final[-1]
            if "mesh" in r or "remat" in r or r.get("pipeline"):
                # the searched plan in one line: mesh, GPipe grid (if any)
                # and the activation-remat level (ISSUE 3)
                bits = []
                if r.get("mesh"):
                    bits.append(f"mesh={tuple(r['mesh'])}")
                if r.get("pipeline"):
                    pp, pdp, m = r["pipeline"]
                    bits.append(f"pipeline pp={pp} dp={pdp} n_micro={m}")
                    # the searched schedule rides next to the grid
                    # (ISSUE 10): gpipe | 1f1b | interleaved(v=...)
                    from flexflow_tpu.parallel.pipeline import \
                        describe_schedule

                    sched = describe_schedule(
                        r.get("schedule") or "",
                        int(r.get("virtual_stages", 1) or 1))
                    bits.append(f"schedule={sched or 'gpipe'}")
                bits.append(f"remat={r.get('remat', 'none')}")
                if r.get("pods"):
                    # pod-level assignment of the hierarchical multi-pod
                    # search (ISSUE 15): pods=N:mode(ga=...), same
                    # vocabulary as Strategy.describe
                    from flexflow_tpu.parallel.strategy import \
                        describe_pods

                    bits.append(describe_pods(tuple(r["pods"])))
                print("searched plan: " + "  ".join(bits))
            if r.get("search_wall_s") is not None:
                # delta-cost engine headline: throughput + cache hit rate
                print(f"delta-cost engine: {r.get('candidates', '?')} "
                      f"candidates in {r['search_wall_s']:.3f} s "
                      f"({r.get('candidates_per_s', '?')}/s), "
                      f"op-cost cache hit rate "
                      f"{r.get('cost_cache_hit_rate', '?')}")
        print("\nbest-so-far trajectory (every ~N/10 iterations):")
        stride = max(len(iters) // 10, 1)
        for r in iters[::stride]:
            print(f"  iter {r.get('iter', '?'):>5}: "
                  f"cost {r['cost_ms']:10.4f} ms  "
                  f"best {r.get('best_ms', r['cost_ms']):10.4f} ms  "
                  f"{'accept' if r.get('accepted') else 'reject'}")
        return
    by_name = {}
    for r in records:
        by_name[r.get("name", r.get("event", "?"))] = \
            by_name.get(r.get("name", r.get("event", "?")), 0) + 1
    print(f"{'event':32s} {'count':>8s}")
    for name, cnt in sorted(by_name.items(), key=lambda kv: -kv[1])[:top]:
        print(f"{name:32s} {cnt:8d}")
    # calibration digest over an event sink (ISSUE 8): the drift sentinel's
    # per-key alerts and any closed-loop repairs that ran
    drifts = [r for r in records
              if r.get("name") == "calibration_drift"
              or r.get("event") == "calibration_drift"]
    repairs = [r for r in records
               if r.get("name") in ("calibration_repair",
                                    "calibration_applied")
               or r.get("event") in ("calibration_repair",
                                     "calibration_applied")]
    if drifts or repairs:
        ops = {}
        for r in drifts:
            a = r.get("args", r)
            if a.get("op") is not None:
                ops[a["op"]] = a.get("ratio")
        line = f"\ncalibration drift: {len(drifts)} alerts"
        if ops:
            worst = max(ops, key=lambda k: max(ops[k] or 1,
                                               1 / (ops[k] or 1)))
            line += (f" over {len(ops)} ops   worst: {worst} "
                     f"(ratio {ops[worst]})")
        print(line)
        for r in repairs[-1:]:
            a = r.get("args", r)
            after = a.get("aggregate_ratio_after")
            print(f"recalibration applied: {a.get('updated', '?')} keys"
                  + (f"   aggregate ratio after repair: {after}"
                     if after is not None else ""))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("file", help="trace JSON / telemetry JSON / JSONL log")
    ap.add_argument("-n", "--top", type=int, default=20,
                    help="rows to show (default 20)")
    args = ap.parse_args(argv)
    kind, payload = load(args.file)
    try:
        if kind == "trace":
            summarize_trace(payload, args.top)
        elif kind == "telemetry":
            summarize_telemetry(payload, args.top)
        else:
            summarize_jsonl(payload, args.top)
    except Exception as e:  # noqa: BLE001 — a cross-PR artifact mismatch
        # must degrade to a notice, never a traceback: telemetry formats
        # and this summary evolve in different PRs (ISSUE 8 satellite)
        print(f"note: {args.file} predates (or postdates) this summary's "
              f"expectations ({type(e).__name__}: {e}); partial output "
              "above")
    return 0


if __name__ == "__main__":
    sys.exit(main())
